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
* layer 0's gate pre-activations (the paper's ``mvm_x`` of the first
  layer) enter in one of three forms, chosen by ``ops.lstm_stack_op`` from
  the input's shape (``LAYER0_FORMS``):

  - ``"narrow"`` — the raw input is at most ``NARROW_MAX_IN`` features
    wide (the GW encoder's one strain sample per step).  It arrives
    lane-dense, ``(B, T*D)`` with the batch on sublanes; a batch block's
    128-lane tile holding step ``s`` is fetched once per 128/D steps, and
    the kernel picks the step's columns out with a lane mask and a lane
    reduction, then forms ``x_t . W_x[0] + b`` as one broadcast
    multiply-add per input column on the VPU;
  - ``"repeat"`` — the input is the same at every step (the decoder's
    RepeatVector latent).  Its projection is one ``(B, 4W)`` block per
    batch row, computed once outside and fetched once per batch block
    (index constant along the wavefront axis);
  - ``"stream"`` — any other input (wider than ``NARROW_MAX_IN``, or
    handed over already padded to the pack width): one big MXU matmul
    outside writes a time-major ``(T, B, 4W)`` gate tensor to HBM, and
    the kernel streams it back one ``(Bb, 4W)`` block per step.

  All three round the product to the compute dtype, widen it, apply the
  per-gate int8 scale and then the bias, in that order.  Only the
  **last** layer's hidden sequence streams out, one ``(Bb, W)`` block per
  step.  Inner layers' projections are computed in-kernel from the
  handed-off ``h`` (their "mvm_x" rides the MXU against VMEM-resident
  weights, matching the paper's per-layer MVM units).

The stack must be homogeneous-packed (``core/pipeline.pack_lstm_stack``):
every layer padded to a common width W.  Zero padding is exact — padded
``W_x``/``W_h`` rows are zero, so padded lanes of a zero-initialized state
stay identically zero and never contaminate real lanes (tested).

Grid = (batch_blocks, T + L - 1); batch is "parallel", the wavefront axis
is "arbitrary" (scratch carries state between consecutive steps).

VMEM budget (fp32, W = padded width, Bb = batch block):
    weights 2*L*W*4W*4 + bias L*4W*4 + state 2*L*Bb*W*4 + streams ~Bb*4W*4*2
(the ``narrow`` form's input tile is Bb*128*4, a quarter of a W=128 gate
block)
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

from repro.kernels.lstm_scan.ops import LANES


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


#: where layer 0's gate pre-activations come from (see the module docstring)
LAYER0_FORMS = ("stream", "repeat", "narrow")

#: widest layer-0 input the kernel projects itself on the VPU (the
#: ``narrow`` form): each input column costs one lane-masked reduction and
#: one broadcast multiply-add over the (Bb, 4W) gate tile per step.  Wider
#: inputs take the ``stream`` form's MXU matmul outside the kernel.  On a
#: TPU v5e, a 2-layer W=128 fp32 segment over 4096 windows of 100 steps
#: (block_b 256) took 5.59 / 5.67 / 5.81 / 6.34 / 7.08 ms a call narrow at
#: D = 1 / 2 / 4 / 8 / 16 against 7.28 ms streamed: 8 is the widest input
#: measured with a clear (13%) gain; at 16 the gain is 3%.
NARROW_MAX_IN = 8


def _narrow_layout(x: jax.Array) -> jax.Array:
    """(B, T, D) raw input -> the ``narrow`` form's lane-dense (B, C) f32.

    Step ``t``'s feature ``k`` sits in column ``t * Dp + k``, ``Dp`` the
    power of two at or above D, so a step's features never straddle a
    128-lane tile; C is a multiple of 128.  For the GW strain (D = 1) this
    is a free reshape plus a pad of the time axis to 128 lanes.  Values are
    exact: the compute dtype widens to f32 without rounding.
    """
    batch, t_len, in_dim = x.shape
    d_pad = _pow2_at_least(in_dim)
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, 0), (0, d_pad - in_dim)))
    x = x.reshape(batch, t_len * d_pad)
    cols = -(-x.shape[1] // LANES) * LANES
    return jnp.pad(x, ((0, 0), (0, cols - x.shape[1])))


def _pow2_at_least(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _lstm_stack_kernel(
    *refs,
    n_layers: int,
    t_len: int,
    width: int,
    form: str,
    in_dim: int,
    sigma: Callable,
    tanh: Callable,
    quantized: bool,
    act_quant: Callable | None,
):
    (
        x0_ref,     # layer-0 input, by form: "stream" (Bb, 4W) gate block at
                    # (t=s, b); "repeat" (Bb, 4W) gate block at b, fetched
                    # once; "narrow" (Bb, 128) f32 lane tile holding step s
        wx_ref,     # (L, W, 4W)   VMEM-resident input projections
        wh_ref,     # (L, W, 4W)   VMEM-resident recurrent weights
        b_ref,      # (L, 1, 4W)   fp32 biases (slot 0: "narrow" form only)
        scale_ref,  # (L, 2, 4) fp32 SMEM per-gate [s_x, s_h] dequant scales
        h0_ref,     # (L, Bb, W)   initial hidden per layer
        c0_ref,     # (L, Bb, W)   initial cell per layer (fp32)
    ) = refs[:7]
    # "narrow" only: (D, 1, 4W) f32 rows of W_x[0] at the compute dtype
    w0_ref = refs[7] if form == "narrow" else None
    (
        hs_ref,     # out: (Bb, W) last layer's hidden, block at (t=s-L+1, b)
        hf_ref,     # out: (L, Bb, W) final hidden per layer
        cf_ref,     # out: (L, Bb, W) final cell per layer (fp32)
        h_scr,      # VMEM scratch (L, Bb, W) compute dtype
        c_scr,      # VMEM scratch (L, Bb, W) fp32
    ) = refs[-5:]
    s = pl.program_id(1)
    prec = dot_precision(h_scr.dtype)
    gate_slices = [slice(g * width, (g + 1) * width) for g in range(4)]

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

    def narrow_gates():
        """Layer 0's per-gate ``x_s . W_x[0] * s_x + b`` from the raw input.

        Step ``s``'s features are picked out of the lane tile with a mask
        and a lane sum (Mosaic lowers no dynamic lane index), then each
        feature column is one broadcast multiply-add over the gate tile:
        the product at fp32, rounded to the compute dtype and widened, the
        per-gate scale, then the bias — the ``stream`` form's order.

        The sum starts from a zero no compiler can fold (``x * 0.0`` is
        NaN for an infinite ``x``).  A compiler that contracts a multiply
        and the add consuming it into one FMA (XLA:CPU, under the
        interpreter) then contracts the first product with that zero,
        which rounds as the product alone, so the bias add still sees the
        rounded product.
        """
        tile = x0_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
        base = (s * _pow2_at_least(in_dim)) % LANES
        cols = [
            jnp.sum(jnp.where(lane == base + k, tile, 0.0), axis=1,
                    keepdims=True)
            for k in range(in_dim)
        ]
        zero = cols[0] * 0.0
        out = []
        for g, sl in enumerate(gate_slices):
            acc = zero + cols[0] * w0_ref[0, :, sl]
            for k in range(1, in_dim):
                acc = acc + cols[k] * w0_ref[k, :, sl]
            acc = acc.astype(h_scr.dtype).astype(jnp.float32)
            if quantized:
                acc = acc * scale_ref[0, 0, g]
            out.append(acc + b_ref[0, :, sl])
        return out

    # Reverse layer order: at step s, layer l must consume h_{l-1}[t = s-l],
    # which is what h_scr[l-1] still holds from step s-1.  Iterating l
    # descending reads it before layer l-1's update this step clobbers it.
    for layer in reversed(range(n_layers)):

        @pl.when((s >= layer) & (s < layer + t_len))
        def _step(layer=layer):
            # per-gate x-terms: each 4W-slice scales its own fp32
            # accumulator ((h @ q) * s, per gate) BEFORE the gate sum —
            # layers whose gates span very different ranges get per-gate
            # int8 grids.  Slicing first commutes with the elementwise
            # scale/bias ops, so uniform (broadcast) scales reproduce the
            # historical whole-accumulator order bit-for-bit.  The bias is
            # loaded per gate from the ref: Mosaic refuses to broadcast a
            # lane slice of a loaded (1, 4W) row to the block
            if layer == 0 and form == "narrow":
                gxs = narrow_gates()
            elif layer == 0:
                # "stream"/"repeat": scales + bias already applied outside
                gx = x0_ref[...]
                gxs = [gx[:, sl] for sl in gate_slices]
            else:
                gx = jnp.dot(
                    h_scr[layer - 1],
                    load_w(wx_ref, layer),
                    preferred_element_type=jnp.float32,
                    precision=prec,
                )
                gxs = []
                for g, sl in enumerate(gate_slices):
                    gxg = gx[:, sl]
                    if quantized:
                        gxg = gxg * scale_ref[layer, 0, g]
                    gxs.append(gxg + b_ref[layer, :, sl])
            hh = jnp.dot(
                h_scr[layer],
                load_w(wh_ref, layer),
                preferred_element_type=jnp.float32,
                precision=prec,
            )
            pre = []
            for g, sl in enumerate(gate_slices):
                hhg = hh[:, sl]
                if quantized:
                    hhg = hhg * scale_ref[layer, 1, g]
                pre.append(gxs[g] + hhg)
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
    x0: jax.Array,     # layer-0 input, by ``form`` (see below)
    w_x: jax.Array,    # (L, W, 4W) packed input projections
    w_h: jax.Array,    # (L, W, 4W) packed recurrent weights
    b: jax.Array,      # (L, 4W) fp32 packed biases
    h0: jax.Array,     # (L, B, W)
    c0: jax.Array,     # (L, B, W) fp32
    *,
    form: str = "stream",
    t_len: int | None = None,
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

    ``x0`` by ``form`` (``LAYER0_FORMS``):

    * ``"stream"``: (T, B, 4W) fp32 — layer 0's mvm_x output with its
      per-gate scales and bias applied, time-major;
    * ``"repeat"``: (B, 4W) fp32 — the same, for an input that is equal at
      every one of ``t_len`` steps;
    * ``"narrow"``: (B, T, D) raw layer-0 input at the compute dtype,
      D <= 128 (``lstm_stack_op`` picks this form up to
      ``NARROW_MAX_IN``); the kernel projects it against the first D rows
      of ``w_x[0]`` and adds the scales and ``b[0]`` itself.

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
    n_layers, width, w4 = w_h.shape
    batch = h0.shape[1]
    assert w4 == 4 * width, w_h.shape
    assert w_x.shape == (n_layers, width, w4), (w_x.shape, width)
    if form == "stream":
        t_len = x0.shape[0]
        assert x0.shape == (t_len, batch, w4), x0.shape
    elif form == "repeat":
        assert t_len is not None and x0.shape == (batch, w4), x0.shape
    elif form == "narrow":
        t_len, in_dim = x0.shape[1], x0.shape[2]
        assert x0.shape[0] == batch and in_dim <= LANES, x0.shape
    else:
        raise ValueError(f"unknown layer-0 form {form!r}; one of {LAYER0_FORMS}")
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
        form=form,
        in_dim=x0.shape[2] if form == "narrow" else 0,
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
    if form == "stream":
        # layer-0 gate stream: clamp past-the-end reads (masked in-kernel)
        x0_spec = pl.BlockSpec(
            (None, block_b, w4), lambda b, s: (jnp.minimum(s, t_last), b, 0)
        )
    elif form == "repeat":
        # one gate block per batch block, fetched once for all its steps
        x0_spec = pl.BlockSpec((block_b, w4), lambda b, s: (b, 0))
    else:
        # the 128-lane tile holding step s (clamped past the end); a new
        # tile is fetched only when s crosses into it
        d_pad = _pow2_at_least(x0.shape[2])
        x0_spec = pl.BlockSpec(
            (block_b, LANES),
            lambda b, s: (b, jnp.minimum(s, t_last) * d_pad // LANES),
        )
    in_specs = [
        x0_spec,
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
    # operands: (x0, w_x, w_h, b, scales, h0, c0[, w0]); outputs: (hs, h_f,
    # c_f)
    operands = [x0, w_x, w_h, b.reshape(n_layers, 1, w4), scales, h0, c0]
    if form == "narrow":
        in_dim = x0.shape[2]
        operands[0] = _narrow_layout(x0)
        # W_x[0]'s real rows at the compute dtype, widened: the kernel's
        # f32 product then rounds exactly as the compute-dtype matmul does
        w0 = w_x[0, :in_dim].astype(h0.dtype).astype(jnp.float32)
        operands.append(w0.reshape(in_dim, 1, w4))
        in_specs.append(pl.BlockSpec((in_dim, 1, w4), lambda b, s: (0, 0, 0)))
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
        input_output_aliases={5: 1, 6: 2} if alias_state else {},
        interpret=interpret,
        name="lstm_stack_wavefront",
    )(*operands)
