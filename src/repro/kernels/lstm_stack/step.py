"""Low-latency step kernel for the fused stack: short chunks, one grid step.

The serving-time critical path the paper optimizes (Sec. III, Fig. 7) is the
*initiation interval* of a streamed sample: a new LIGO strain sample arrives
every sampling period and must advance the resident LSTM state with minimal
latency.  The wavefront kernel (``lstm_stack.py``) is built for throughput —
its grid walks ``T + L - 1`` sequential steps and its layer-0 input
projection is a separate XLA matmul whose ``(T, B, 4W)`` result round-trips
through HBM.  Both choices are right at window scale and wrong at chunk
scale: at ``T = 1`` the pre-kernel matmul is a tiny kernel launch plus an
HBM round-trip that costs more than the math, and the wavefront grid
degenerates to ``L`` masked steps.

This kernel is the step-scale specialization, for ``T in {1..chunk_len}``:

* **one grid step per batch block** — the whole chunk runs inside a single
  kernel invocation: one compiled cell body iterated over ``t`` with
  per-layer ``h``/``c`` carried as *values* (no stage-axis scratch, no
  ``pl.when`` masking, no revisited output blocks);
* **layer 0's input projection happens in-kernel** — the raw chunk
  (time-major ``(T, B, W)`` inside the kernel) is the only streamed input;
  nothing the size of the gate tensor ever leaves the chip;
* **optionally one fused gate matmul per cell** (``fuse_gates``): the two
  gate MVMs become a single ``[x_or_h_prev ; h_l] @ [W_x ; W_h]``
  ``(Bb, 2W) @ (2W, 4W)`` MXU issue — halving matmul issues exactly where
  the MXU is most underfed (B = 1, T = 1).

Numerics contract: with ``fuse_gates=False`` the kernel performs the
wavefront kernel's per-cell operations in the identical order (same dots,
same ``preferred_element_type``, same per-gate scale/bias placement, same
fp32 cell tail).  At ``T = 1`` — the serving-critical sample-by-sample
push — it is **bit-for-bit equal** to ``lstm_stack`` on every weight
dtype, regression-tested in CPU interpret mode, where the separate-dot
path is the default.  At ``T > 1`` the two kernels are distinct programs
(an iterated loop body here, a sequential grid there) and XLA emits each
program's dot reductions independently, so equality is ~1 ulp rather than
bitwise; any FIXED chunking replays bit-identically, which is what the
``push_many`` == sequential-replay equality builds on.
``fuse_gates=True`` additionally reorders the gate sum's reduction (one
contraction over ``2W`` instead of two over ``W``); it is the default on
compiled TPU backends, where the MXU issue-rate argument applies.  There,
``chip_smoke.py`` checks that served GW window scores still equal the
batch (wavefront) scores bit for bit at chunk lengths 1, 25 and 100.
Quantized (int8) packs always use the separate-dot path: ``s_x`` and
``s_h`` scale two different fp32 accumulators, which a fused contraction
would mix.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import EXACT, kernel_safe, make_act_quant
from repro.kernels.lstm_scan.ops import _on_cpu, choose_blocking

from .lstm_stack import dot_precision
from .ops import check_packed_weight_dtype, normalize_scales

#: hard ceiling on T*L cell updates per call: the step kernel executes the
#: chunk strictly sequentially (its win is latency, not throughput), so a
#: very long chunk is always the wrong tool — that regime belongs to the
#: wavefront kernel (``core/backends`` routes it there via chunk_len)
MAX_STEP_UNROLL = 512


def _lstm_stack_step_kernel(
    x_ref,      # (T, Bb, W)    raw layer-0 chunk, time-major, compute dtype
    wx_ref,     # (L, W, 4W)    VMEM-resident input projections
    wh_ref,     # (L, W, 4W)    VMEM-resident recurrent weights
    b_ref,      # (L, 1, 4W)    fp32 biases
    scale_ref,  # (L, 2, 4)     fp32 SMEM per-gate [s_x, s_h] dequant scales
    h0_ref,     # (L, Bb, W)    initial hidden per layer
    c0_ref,     # (L, Bb, W)    initial cell per layer (fp32)
    hs_ref,     # out: (T, Bb, W) last layer's hidden chunk, time-major
    hf_ref,     # out: (L, Bb, W) final hidden per layer
    cf_ref,     # out: (L, Bb, W) final cell per layer (fp32)
    *,
    n_layers: int,
    t_len: int,
    width: int,
    sigma: Callable,
    tanh: Callable,
    quantized: bool,
    fuse_gates: bool,
    act_quant: Callable | None,
):
    compute = h0_ref.dtype
    prec = dot_precision(compute)

    def load_w(w_ref, layer):
        w = w_ref[layer]
        return w if w.dtype == compute else w.astype(compute)

    # per-layer state as plain values: the whole chunk runs in one grid
    # step, so h/c live in registers/VMEM with no scratch round-trips
    h = [h0_ref[layer] for layer in range(n_layers)]
    c = [c0_ref[layer] for layer in range(n_layers)]

    if fuse_gates:
        # hoisted once per kernel call: the contiguous [W_x ; W_h] each
        # fused gate matmul contracts against (VMEM->VMEM, never HBM)
        w_cat = [
            jnp.concatenate([load_w(wx_ref, layer), load_w(wh_ref, layer)], axis=0)
            for layer in range(n_layers)
        ]

    def cell(t, h, c):
        """One timestep over all layers (ascending: layer l consumes
        h_{l-1}[t], which layer l-1 just produced this timestep)."""
        h, c = list(h), list(c)
        for layer in range(n_layers):
            if fuse_gates:
                x_in = x_ref[t] if layer == 0 else h[layer - 1]
                hcat = jnp.concatenate([x_in, h[layer]], axis=1)
                gx = jnp.dot(
                    hcat, w_cat[layer], preferred_element_type=jnp.float32,
                    precision=prec,
                )
                hh = None
            else:
                if layer == 0:
                    # layer 0's input projection: a ref-level load of
                    # timestep t (Mosaic lowers no value-level dynamic
                    # slice).  The accumulator is rounded to the compute
                    # dtype before widening, as the wavefront path's
                    # out-of-kernel ``(xs @ w0).astype(f32)`` rounds it; the
                    # explicit round trip keeps XLA from folding the widen
                    # into the dot (which would skip the bf16 rounding)
                    gx = jnp.dot(
                        x_ref[t], load_w(wx_ref, 0),
                        preferred_element_type=jnp.float32,
                        precision=prec,
                    ).astype(compute).astype(jnp.float32)
                else:
                    gx = jnp.dot(
                        h[layer - 1], load_w(wx_ref, layer),
                        preferred_element_type=jnp.float32,
                        precision=prec,
                    )
                hh = jnp.dot(
                    h[layer], load_w(wh_ref, layer),
                    preferred_element_type=jnp.float32,
                    precision=prec,
                )
            # per-gate tail: scale each 4W-slice on its own accumulator
            # BEFORE the gate sum (per-gate int8 grids), bias placement
            # identical to the wavefront kernel: (gx*s_x + b) + hh*s_h
            pre = []
            for g in range(4):
                sl = slice(g * width, (g + 1) * width)
                gxg = gx[:, sl]
                if quantized:
                    gxg = gxg * scale_ref[layer, 0, g]
                # per-gate ref load, as in the wavefront kernel (Mosaic
                # refuses to broadcast a lane slice of a loaded bias row)
                gxg = gxg + b_ref[layer, :, sl]
                if hh is not None:
                    hhg = hh[:, sl]
                    if quantized:
                        hhg = hhg * scale_ref[layer, 1, g]
                    gxg = gxg + hhg
                pre.append(gxg)
            i = sigma(pre[0])
            f = sigma(pre[1])
            g_ = tanh(pre[2])
            o = sigma(pre[3])
            c_new = f * c[layer] + i * g_      # fp32 tail (32-bit cell)
            h_new = o * tanh(c_new)
            if act_quant is not None:
                # hand-off fake-quant, identical placement to the wavefront
                # kernel (h only — the fp32 cell carry stays full-width)
                h_new = act_quant(h_new)
            h_new = h_new.astype(compute)
            c[layer] = c_new
            h[layer] = h_new
        return h, c

    if t_len == 1:
        # the serving-critical T=1 push: straight-line code, no loop
        h, c = cell(0, h, c)
        hs_ref[0] = h[n_layers - 1].astype(hs_ref.dtype)
    else:
        # one compiled loop body iterated over t — NOT a python unroll.
        # Bitwise reproducibility again: T copies of the cell would give
        # the compiler T independently-optimizable instances of the same
        # dots, and instance-dependent codegen shifts summation order;
        # one body iterated computes every timestep with literally the
        # same code, exactly like the wavefront kernel's sequential grid.
        def body(t, carry):
            h, c = carry[:n_layers], carry[n_layers:]
            h, c = cell(t, h, c)
            hs_ref[t] = h[n_layers - 1].astype(hs_ref.dtype)
            return (*h, *c)

        out = jax.lax.fori_loop(0, t_len, body, (*h, *c))
        h, c = out[:n_layers], out[n_layers:]

    for layer in range(n_layers):
        hf_ref[layer] = h[layer].astype(hf_ref.dtype)
        cf_ref[layer] = c[layer]


def lstm_stack_step(
    xs: jax.Array,     # (B, T, W) raw layer-0 chunk, batch-major, pre-padded
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
    interpret: bool = False,
    alias_state: bool = True,
    fuse_gates: bool = False,
    act_quant: Callable | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run a short chunk through the whole stack in one grid step per batch
    block.  Shapes pre-padded by the op wrapper; returns
    (hs_last: (B, T, W), h_final: (L, B, W), c_final fp32: (L, B, W)).

    Unlike ``lstm_stack`` the input is the *raw* chunk — layer 0's gate
    projection happens in-kernel, so no ``(T, B, 4W)`` tensor ever exists.
    Inside the kernel the chunk is time-major, so timestep ``t`` is a
    leading-axis ref load; at the serving-critical T=1 the swap in and out
    is a free reshape.  ``alias_state`` maps h0/c0 onto the finals exactly
    like the wavefront kernel, so a persistent-state serving loop carries
    (h, c) with zero per-call state allocations.
    """
    batch, t_len, w4 = xs.shape[0], xs.shape[1], 4 * xs.shape[2]
    width = xs.shape[2]
    n_layers = w_h.shape[0]
    assert w_h.shape == (n_layers, width, w4), (w_h.shape, width)
    assert w_x.shape == (n_layers, width, w4), (w_x.shape, width)
    if t_len * n_layers > MAX_STEP_UNROLL:
        raise ValueError(
            f"lstm_stack_step runs T*L={t_len * n_layers} sequential cells "
            f"in one call (> {MAX_STEP_UNROLL}); chunks this long belong to "
            "the wavefront kernel — lower the plan's chunk_len"
        )
    quantized = scales is not None
    if w_h.dtype == jnp.int8 and not quantized:
        raise ValueError(
            "lstm_stack_step: int8 weights need per-layer dequant `scales`; "
            "pack them with pack_stack(weight_dtype='int8')"
        )
    if quantized and fuse_gates:
        raise ValueError(
            "fuse_gates is incompatible with quantized packs: s_x and s_h "
            "scale two different accumulators, which one fused contraction "
            "would mix"
        )
    if quantized:
        # canonical per-gate (L, 2, 4); legacy (L, 2) packs broadcast
        scales = normalize_scales(scales, n_layers)
    else:  # uniform operand list; never read in-kernel
        scales = jnp.ones((n_layers, 2, 4), jnp.float32)
    if block_b is None:
        block_b = batch
    assert batch % block_b == 0, (batch, block_b)
    n_b = batch // block_b

    kernel = functools.partial(
        _lstm_stack_step_kernel,
        n_layers=n_layers,
        t_len=t_len,
        width=width,
        sigma=sigma,
        tanh=tanh,
        quantized=quantized,
        fuse_gates=fuse_gates,
        act_quant=act_quant,
    )
    out_shape = [
        jax.ShapeDtypeStruct((t_len, batch, width), h0.dtype),        # hs
        jax.ShapeDtypeStruct((n_layers, batch, width), h0.dtype),     # h_f
        jax.ShapeDtypeStruct((n_layers, batch, width), jnp.float32),  # c_f
    ]
    in_specs = [
        pl.BlockSpec((t_len, block_b, width), lambda b: (0, b, 0)),
        pl.BlockSpec((n_layers, width, w4), lambda b: (0, 0, 0)),
        pl.BlockSpec((n_layers, width, w4), lambda b: (0, 0, 0)),
        pl.BlockSpec((n_layers, 1, w4), lambda b: (0, 0, 0)),
        pl.BlockSpec((n_layers, 2, 4), lambda b: (0, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((n_layers, block_b, width), lambda b: (0, b, 0)),
        pl.BlockSpec((n_layers, block_b, width), lambda b: (0, b, 0)),
    ]
    out_specs = [
        pl.BlockSpec((t_len, block_b, width), lambda b: (0, b, 0)),
        pl.BlockSpec((n_layers, block_b, width), lambda b: (0, b, 0)),
        pl.BlockSpec((n_layers, block_b, width), lambda b: (0, b, 0)),
    ]
    hs, h_f, c_f = pl.pallas_call(
        kernel,
        grid=(n_b,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        # operands: (xs, w_x, w_h, b, scales, h0, c0); outputs: (hs, h_f, c_f)
        input_output_aliases={5: 1, 6: 2} if alias_state else {},
        interpret=interpret,
        name="lstm_stack_step",
    )(jnp.swapaxes(xs, 0, 1), w_x, w_h, b.reshape(n_layers, 1, w4), scales,
      h0, c0)
    return jnp.swapaxes(hs, 0, 1), h_f, c_f


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_b", "acts", "interpret", "alias_state", "weight_dtype",
        "fuse_gates", "act_bits",
    ),
)
def lstm_stack_step_op(
    xs: jax.Array,       # (B, T, W) layer-0 chunk, pre-padded to the pack width
    stacked: dict,       # pack_stack output: w_x/w_h/b[, scales]
    h0: jax.Array,       # (L, B, W)
    c0: jax.Array,       # (L, B, W)
    *,
    block_b: int | None = None,
    acts=EXACT,
    interpret: bool | None = None,
    alias_state: bool = True,
    weight_dtype: str = "fp32",
    fuse_gates: bool | None = None,
    act_bits: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Step-path twin of ``lstm_stack_op`` for short chunks.

    Differences on the hot path: no out-of-kernel mvm_x (layer 0 projects
    in-kernel from the raw chunk), no gate-sized time-major transposes (the
    raw chunk's is a reshape at T=1), and one grid step per batch block.
    Returns the same
    (hs: (B, T, W), h_final: (L, B, W), c_final fp32) triple.

    ``fuse_gates=None`` resolves to the numerics contract documented in the
    kernel: separate dots (bit-for-bit vs the wavefront kernel) in
    interpret mode, the single fused gate matmul on compiled TPU backends.
    Quantized packs always take separate dots.
    """
    if interpret is None:
        interpret = _on_cpu()
    batch, t_len, width = xs.shape
    assert stacked["w_h"].shape[1] == width, (stacked["w_h"].shape, width)
    check_packed_weight_dtype(stacked, weight_dtype, h0.dtype)
    quantized = weight_dtype == "int8"
    if fuse_gates is None:
        fuse_gates = (not interpret) and not quantized

    # DEVICE blocking even in interpret mode (unlike lstm_stack_op): the
    # batch pads to sublane multiples everywhere, so a B=1 push and a
    # B<=8 coalesced push_many execute the SAME program shape — their
    # bit-equality is then row selection inside one compiled program, not
    # a fragile cross-program property (and interpret numerics match the
    # device's padded layout).  Zero-padded rows are inert: zero weights
    # rows keep padded lanes zero, and the op slices real rows back out.
    batch_p, block_b = choose_blocking(batch, block_b, interpret=False)
    xs_p = jnp.pad(xs, ((0, batch_p - batch), (0, 0), (0, 0)))
    h0_p = jnp.pad(h0, ((0, 0), (0, batch_p - batch), (0, 0)))
    c0_p = jnp.pad(c0, ((0, 0), (0, batch_p - batch), (0, 0)))

    acts_k = kernel_safe(acts)
    hs, h_f, c_f = lstm_stack_step(
        xs_p,
        stacked["w_x"],
        stacked["w_h"],
        stacked["b"].astype(jnp.float32),
        h0_p,
        c0_p.astype(jnp.float32),
        scales=stacked["scales"] if quantized else None,
        block_b=block_b,
        sigma=acts_k.sigma,
        tanh=acts_k.tanh,
        interpret=interpret,
        alias_state=alias_state,
        fuse_gates=fuse_gates,
        act_quant=make_act_quant(act_bits) if act_bits is not None else None,
    )
    return hs[:batch], h_f[:, :batch], c_f[:, :batch]
