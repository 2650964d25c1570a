"""Fused multi-layer wavefront LSTM stack — one Pallas call for L layers.

This is the paper's Sec. III-B/III-D coarse-grained pipeline (Fig. 7)
collapsed into a single TPU kernel: the grid's sequential axis is the
*wavefront step* ``s in [0, T + L - 1)``, and at step ``s`` layer ``l``
processes its timestep ``t = s - l`` (when ``0 <= t < T``).  Layer ``l+1``
therefore consumes ``h_l[t]`` exactly one grid step after layer ``l`` emits
it — the hand-off is a read of layer ``l``'s VMEM state slot, never an HBM
round-trip.  Compare with per-layer execution (kernels/lstm_scan called L
times), where every layer writes its full ``(T, B, H)`` hidden sequence to
HBM and the next layer reads it back, plus per-layer pad/transpose glue.

TPU translation of the paper's structures:

* all L layers' ``W_h`` *and* ``W_x`` are **VMEM-resident** for the whole
  call (BlockSpec index maps constant in ``s``) — the analogue of every
  FPGA layer-unit holding its weights in fabric simultaneously;
* per-layer ``h``/``c`` live in **VMEM scratch with a leading stage axis**
  ``(L, Bb, W)``, carried across grid steps — nothing recurrent ever
  leaves the chip;
* the layer loop is unrolled **in reverse** inside the kernel body, so
  layer ``l`` reads ``h_scr[l-1]`` *before* layer ``l-1`` overwrites it
  this step: the one-step-delayed hand-off falls out of program order with
  no double buffer;
* only layer 0's input projection ``xW`` (the paper's ``mvm_x``, one big
  MXU matmul done outside) streams in, one ``(Bb, 4W)`` block per step,
  and only the **last** layer's hidden sequence streams out, one
  ``(Bb, W)`` block per step.  Inner layers' projections are computed
  in-kernel from the handed-off ``h`` (their "mvm_x" rides the MXU against
  VMEM-resident weights, matching the paper's per-layer MVM units).

The stack must be homogeneous-packed (``core/pipeline.pack_lstm_stack``):
every layer padded to a common width W.  Zero padding is exact — padded
``W_x``/``W_h`` rows are zero, so padded lanes of a zero-initialized state
stay identically zero and never contaminate real lanes (tested).

Grid = (batch_blocks, T + L - 1); batch is "parallel", the wavefront axis
is "arbitrary" (scratch carries state between consecutive steps).

VMEM budget (fp32, W = padded width, Bb = batch block):
    weights 2*L*W*4W*4 + bias L*4W*4 + state 2*L*Bb*W*4 + streams ~Bb*4W*4*2
For the GW nominal model (L=2 per segment, W=128, Bb=256) that is ~1.3 MB —
far below the ~16 MB/core budget.  The weight term — the dominant VMEM
tenant at serving batch sizes — shrinks 2x with bf16 and 4x with int8
storage (paper Sec. IV-A: 16-bit fixed weights, 32-bit cell): quantized
codes stay resident, per-layer dequant scales sit in SMEM, and the cast to
compute dtype rides the tile on its way into the MXU.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def dot_precision(compute_dtype) -> jax.lax.Precision | None:
    """Contraction precision for the fused stack's matmuls (both kernels and
    the out-of-kernel layer-0 projection): full fp32 at an fp32 compute
    dtype.  XLA's TPU default for fp32 operands is one bf16 pass, which
    would make fp32 weight storage numerically a bf16 model.  bf16 operands
    are exact in one pass, and Mosaic refuses fp32 precision for them.  The
    CPU ignores the setting."""
    if jnp.dtype(compute_dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def _lstm_stack_kernel(
    xw_ref,    # (Bb, 4W)     layer-0 gate stream, block at (t=s, b)
    wx_ref,    # (L, W, 4W)   VMEM-resident input projections (slot 0 unused)
    wh_ref,    # (L, W, 4W)   VMEM-resident recurrent weights
    b_ref,     # (L, 1, 4W)   fp32 biases (slot 0 folded into the xw stream)
    scale_ref,  # (L, 2, 4) fp32 SMEM per-gate [s_x, s_h] dequant scales
    h0_ref,    # (L, Bb, W)   initial hidden per layer
    c0_ref,    # (L, Bb, W)   initial cell per layer (fp32)
    hs_ref,    # out: (Bb, W) last layer's hidden, block at (t=s-L+1, b)
    hf_ref,    # out: (L, Bb, W) final hidden per layer
    cf_ref,    # out: (L, Bb, W) final cell per layer (fp32)
    h_scr,     # VMEM scratch (L, Bb, W) compute dtype
    c_scr,     # VMEM scratch (L, Bb, W) fp32
    *,
    n_layers: int,
    t_len: int,
    width: int,
    sigma: Callable,
    tanh: Callable,
    quantized: bool,
    act_quant: Callable | None,
):
    s = pl.program_id(1)
    prec = dot_precision(h_scr.dtype)

    @pl.when(s == 0)
    def _init():
        h_scr[...] = h0_ref[...]
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    def load_w(w_ref, layer):
        """A layer's weight tile at the compute dtype.

        Weights stay int8/bf16-resident in VMEM for the whole call — this
        cast happens tile-by-tile on the way into the MXU (int8 -> bf16 is
        exact: |q| <= 127 < 2^8 mantissa bits).  The dequant *scale* is
        applied to the fp32 matmul result (see below), never to the weight
        tile, so the stored codes are what the MXU consumes.
        """
        w = w_ref[layer]
        return w if w.dtype == h_scr.dtype else w.astype(h_scr.dtype)

    # Reverse layer order: at step s, layer l must consume h_{l-1}[t = s-l],
    # which is what h_scr[l-1] still holds from step s-1.  Iterating l
    # descending reads it before layer l-1's update this step clobbers it.
    for layer in reversed(range(n_layers)):

        @pl.when((s >= layer) & (s < layer + t_len))
        def _step(layer=layer):
            if layer == 0:
                # streamed mvm_x: scales + bias already applied outside
                gx = xw_ref[...]
            else:
                gx = jnp.dot(
                    h_scr[layer - 1],
                    load_w(wx_ref, layer),
                    preferred_element_type=jnp.float32,
                    precision=prec,
                )
            hh = jnp.dot(
                h_scr[layer],
                load_w(wh_ref, layer),
                preferred_element_type=jnp.float32,
                precision=prec,
            )
            # per-gate tail: each 4W-slice scales its own fp32 accumulator
            # ((h @ q) * s, per gate) BEFORE the gate sum — layers whose
            # gates span very different ranges get per-gate int8 grids.
            # Slicing first commutes with the elementwise scale/bias ops,
            # so uniform (broadcast) scales reproduce the historical
            # whole-accumulator order bit-for-bit.
            # the bias is loaded per gate from the ref: Mosaic refuses to
            # broadcast a lane slice of a loaded (1, 4W) row to the block
            pre = []
            for g in range(4):
                sl = slice(g * width, (g + 1) * width)
                gxg = gx[:, sl]
                if layer > 0:
                    if quantized:
                        gxg = gxg * scale_ref[layer, 0, g]
                    gxg = gxg + b_ref[layer, :, sl]
                hhg = hh[:, sl]
                if quantized:
                    hhg = hhg * scale_ref[layer, 1, g]
                pre.append(gxg + hhg)
            i = sigma(pre[0])
            f = sigma(pre[1])
            g = tanh(pre[2])
            o = sigma(pre[3])
            c = f * c_scr[layer] + i * g      # fp32 tail (paper: 32-bit cell)
            h = o * tanh(c)
            if act_quant is not None:
                # activation fake-quant on the layer hand-off (paper fixes
                # activations to 16 bits; the cell carry above stays fp32)
                h = act_quant(h)
            h = h.astype(h_scr.dtype)
            c_scr[layer] = c
            h_scr[layer] = h
            if layer == n_layers - 1:
                hs_ref[...] = h.astype(hs_ref.dtype)

        @pl.when(s == layer + t_len - 1)
        def _finalize(layer=layer):
            hf_ref[layer] = h_scr[layer].astype(hf_ref.dtype)
            cf_ref[layer] = c_scr[layer]


def lstm_stack(
    xw0: jax.Array,    # (T, B, 4W) fp32 — layer 0 mvm_x output + bias, time-major
    w_x: jax.Array,    # (L, W, 4W) packed input projections
    w_h: jax.Array,    # (L, W, 4W) packed recurrent weights
    b: jax.Array,      # (L, 4W) fp32 packed biases
    h0: jax.Array,     # (L, B, W)
    c0: jax.Array,     # (L, B, W) fp32
    *,
    scales: jax.Array | None = None,  # (L, 2) or (L, 2, 4) fp32, int8 only
    block_b: int | None = None,
    sigma: Callable = jax.nn.sigmoid,
    tanh: Callable = jnp.tanh,
    act_quant: Callable | None = None,
    interpret: bool = False,
    alias_state: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run the fused L-layer wavefront. Shapes pre-padded by ops.py (W a lane
    multiple, B a block multiple on device).  Returns
    (hs_last: (T, B, W), h_final: (L, B, W), c_final fp32: (L, B, W)).

    Weight storage may be narrower than the compute dtype: bf16 weights are
    cast up tile-by-tile into the MXU; int8 weights additionally require
    ``scales`` — symmetric dequant factors, kept in SMEM and applied to the
    fp32 matmul accumulator (``(h @ q) * s``), so the VMEM-resident weight
    arrays stay at 1 byte/element for the whole call.  Scales are per-gate
    ``(L, 2, 4)`` — one grid per [i|f|g|o] slice of each matrix; legacy
    per-matrix ``(L, 2)`` packs broadcast to the same shape (bit-for-bit
    with their historical whole-accumulator scaling).  The cell state ``c``
    is carried fp32 regardless (paper Sec. IV-A).

    ``alias_state`` maps ``h0 -> h_final`` and ``c0 -> c_final`` via
    ``input_output_aliases``: the kernel may write the final state in place
    over the initial state, so a persistent-state serving loop (feed the
    finals back as the next call's initials, donate at the jit boundary)
    carries (h, c) with zero per-call state allocations.  Safe because each
    batch block reads ``h0``/``c0`` exactly once, at its first wavefront
    step, strictly before any final-state write for that block.
    """
    t_len, batch, w4 = xw0.shape
    width = w4 // 4
    n_layers = w_h.shape[0]
    assert w_h.shape == (n_layers, width, w4), (w_h.shape, width)
    assert w_x.shape == (n_layers, width, w4), (w_x.shape, width)
    quantized = scales is not None
    if w_h.dtype == jnp.int8 and not quantized:
        raise ValueError(
            "lstm_stack: int8 weights need per-layer dequant `scales`; pack "
            "them with pack_stack(weight_dtype='int8') instead of casting"
        )
    if block_b is None:
        block_b = batch
    assert batch % block_b == 0, (batch, block_b)
    n_b = batch // block_b
    n_s = t_len + n_layers - 1
    if quantized:
        if scales.ndim == 2:  # legacy per-matrix pack: broadcast per gate
            scales = jnp.broadcast_to(scales[:, :, None], (n_layers, 2, 4))
        assert scales.shape == (n_layers, 2, 4), scales.shape
    else:  # uniform operand list; ones are never read in-kernel
        scales = jnp.ones((n_layers, 2, 4), jnp.float32)

    kernel = functools.partial(
        _lstm_stack_kernel,
        n_layers=n_layers,
        t_len=t_len,
        width=width,
        sigma=sigma,
        tanh=tanh,
        quantized=quantized,
        act_quant=act_quant,
    )
    grid = (n_b, n_s)
    t_last = t_len - 1
    lag = n_layers - 1

    out_shape = [
        jax.ShapeDtypeStruct((t_len, batch, width), h0.dtype),      # hs_last
        jax.ShapeDtypeStruct((n_layers, batch, width), h0.dtype),   # h_final
        jax.ShapeDtypeStruct((n_layers, batch, width), jnp.float32),  # c_final
    ]
    in_specs = [
        # layer-0 gate stream: clamp past-the-end reads (masked in-kernel)
        pl.BlockSpec(
            (None, block_b, w4), lambda b, s: (jnp.minimum(s, t_last), b, 0)
        ),
        pl.BlockSpec((n_layers, width, w4), lambda b, s: (0, 0, 0)),
        pl.BlockSpec((n_layers, width, w4), lambda b, s: (0, 0, 0)),
        pl.BlockSpec((n_layers, 1, w4), lambda b, s: (0, 0, 0)),
        # dequant scales: L*2*4 scalars, SMEM-resident (scalar loads, no VPU
        # lane traffic)
        pl.BlockSpec(
            (n_layers, 2, 4), lambda b, s: (0, 0, 0), memory_space=pltpu.SMEM
        ),
        pl.BlockSpec((n_layers, block_b, width), lambda b, s: (0, b, 0)),
        pl.BlockSpec((n_layers, block_b, width), lambda b, s: (0, b, 0)),
    ]
    out_specs = [
        # the last layer emits timestep t = s - (L-1); the clamped index
        # revisits block 0 during the fill steps, which never write, so the
        # block is only flushed once valid data landed in it
        pl.BlockSpec(
            (None, block_b, width),
            lambda b, s: (jnp.clip(s - lag, 0, t_last), b, 0),
        ),
        pl.BlockSpec((n_layers, block_b, width), lambda b, s: (0, b, 0)),
        pl.BlockSpec((n_layers, block_b, width), lambda b, s: (0, b, 0)),
    ]
    scratch_shapes = [
        pltpu.VMEM((n_layers, block_b, width), h0.dtype),
        pltpu.VMEM((n_layers, block_b, width), jnp.float32),
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        # operands: (xw0, w_x, w_h, b, scales, h0, c0); outputs: (hs, h_f, c_f)
        input_output_aliases={5: 1, 6: 2} if alias_state else {},
        interpret=interpret,
        name="lstm_stack_wavefront",
    )(xw0, w_x, w_h, b.reshape(n_layers, 1, w4), scales, h0, c0)
