"""Fused LSTM recurrent-sublayer scan — Pallas TPU kernel.

This is the TPU translation of the paper's dependency-bound sub-layer
(Sec. III-C): ``mvm_h`` + gate activations + element-wise tail, iterated over
timesteps.  The paper minimizes this loop's initiation interval by giving it
as many multipliers as the budget allows and keeping the loop "rewound"
(zero drain between iterations).  The TPU equivalents implemented here:

* ``h_t`` / ``c_t`` live in **VMEM scratch across grid steps** — zero HBM
  traffic for the recurrent state (the FPGA keeps them in registers/BRAM).
* ``W_h`` is **VMEM-resident** for the whole scan (BlockSpec index map is
  constant in ``t``), exactly like weights pinned in FPGA fabric.
* gates + tail are **fused** into the same kernel body — one VPU pass per
  timestep, no gate tensors ever materialize in HBM.
* the input projection ``xW`` (the paper's ``mvm_x`` sub-layer) is computed
  *outside* as one large MXU matmul over all timesteps and streamed in one
  ``(Bb, 4H)`` block per grid step — it has no recurrent dependency, so it
  pipelines ahead of the scan just as the paper overlaps the two sub-layers.
* ``c_t`` is carried in fp32 (the paper's 32-bit cell state) regardless of
  the compute dtype.

Grid = (batch_blocks, T): the batch dimension is embarrassingly parallel
("parallel"), the time dimension is the sequential recurrence ("arbitrary",
innermost so scratch carries state between consecutive steps of the same
batch block).

VMEM budget per core (bf16 compute, fp32 state):
    W_h: H*4H*2  +  xW block: Bb*4H*4  +  h,c scratch: 2*Bb*H*4  + out: Bb*H*2
For the GW models (H<=32 padded to 128) this is ~0.6 MB at Bb=256 — far under
the ~16 MB/core VMEM budget; block_b is chosen by ops.py accordingly.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lstm_scan_kernel(
    xw_ref,    # (Bb, 4H)  fp32 block at (t, b)
    wh_ref,    # (H, 4H)   VMEM-resident weights
    h0_ref,    # (Bb, H)
    c0_ref,    # (Bb, H)   fp32
    hs_ref,    # out: (Bb, H) block at (t, b)
    hf_ref,    # out: (Bb, H) final hidden
    cf_ref,    # out: (Bb, H) final cell (fp32)
    h_scr,     # VMEM scratch (Bb, H) compute dtype
    c_scr,     # VMEM scratch (Bb, H) fp32
    *,
    hidden: int,
    sigma: Callable,
    tanh: Callable,
):
    t = pl.program_id(1)
    n_t = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...]
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    h_prev = h_scr[...]
    # mvm_h on the MXU; accumulate in fp32 with the streamed-in xW block
    gates = xw_ref[...] + jnp.dot(
        h_prev, wh_ref[...], preferred_element_type=jnp.float32
    )
    i = sigma(gates[:, 0 * hidden : 1 * hidden])
    f = sigma(gates[:, 1 * hidden : 2 * hidden])
    g = tanh(gates[:, 2 * hidden : 3 * hidden])
    o = sigma(gates[:, 3 * hidden : 4 * hidden])
    c = f * c_scr[...] + i * g          # fp32 tail (paper: 32-bit cell)
    h = (o * tanh(c)).astype(h_scr.dtype)

    c_scr[...] = c
    h_scr[...] = h
    hs_ref[...] = h.astype(hs_ref.dtype)

    @pl.when(t == n_t - 1)
    def _final():
        hf_ref[...] = h.astype(hf_ref.dtype)
        cf_ref[...] = c


def lstm_scan(
    xw: jax.Array,      # (T, B, 4H) fp32 — mvm_x output + bias, time-major
    w_h: jax.Array,     # (H, 4H)
    h0: jax.Array,      # (B, H)
    c0: jax.Array,      # (B, H) fp32
    *,
    block_b: int | None = None,
    sigma: Callable = jax.nn.sigmoid,
    tanh: Callable = jnp.tanh,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run the fused recurrent scan. Shapes must be pre-padded by ops.py:
    H a multiple of 128 (TPU lanes) and B a multiple of block_b on device.
    Returns (hs: (T, B, H), h_final: (B, H), c_final fp32: (B, H)).
    """
    t_len, batch, h4 = xw.shape
    hidden = h4 // 4
    assert w_h.shape == (hidden, h4), (w_h.shape, hidden)
    if block_b is None:
        block_b = batch
    assert batch % block_b == 0, (batch, block_b)
    n_b = batch // block_b

    kernel = functools.partial(
        _lstm_scan_kernel, hidden=hidden, sigma=sigma, tanh=tanh
    )
    grid = (n_b, t_len)

    out_shape = [
        jax.ShapeDtypeStruct((t_len, batch, hidden), h0.dtype),  # hs
        jax.ShapeDtypeStruct((batch, hidden), h0.dtype),         # h_final
        jax.ShapeDtypeStruct((batch, hidden), jnp.float32),      # c_final
    ]
    in_specs = [
        pl.BlockSpec((None, block_b, h4), lambda b, t: (t, b, 0)),
        pl.BlockSpec((hidden, h4), lambda b, t: (0, 0)),
        pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
        pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
    ]
    out_specs = [
        pl.BlockSpec((None, block_b, hidden), lambda b, t: (t, b, 0)),
        pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
        pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_b, hidden), h0.dtype),
        pltpu.VMEM((block_b, hidden), jnp.float32),
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
        interpret=interpret,
        name="lstm_scan",
    )(xw, w_h, h0, c0)
