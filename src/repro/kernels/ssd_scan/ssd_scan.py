"""Chunked Mamba-2 SSD scan — Pallas TPU kernel.

The state-space-duality recurrence (per batch, per head; scalar decay per
head as in Mamba-2):

    S_t = exp(alpha_t) * S_{t-1} + dt_t * (x_t outer B_t)        S in R^{PxN}
    y_t = C_t . S_t

is the same "recurrent sub-layer" shape as the paper's LSTM loop: a small
dependency-bound update that must not round-trip HBM.  The chunked algorithm
converts the time loop into MXU matmuls (intra-chunk, fully parallel — the
analogue of the paper's ``mvm_x`` sub-layer) plus a per-chunk state carry
(the dependency-bound part, kept in VMEM scratch across grid steps):

    intra:  Y_intra = [ tril(exp(cum_i - cum_j)) . (C B^T) . dt_j ] @ X
    inter:  Y_inter = (C . exp(cum)) @ S_prev^T
    carry:  S_new   = exp(cum_L) S_prev + (X . dt . exp(cum_L - cum))^T @ B

Grid = (batch*heads, n_chunks): heads are parallel, chunks sequential with
S resident in VMEM — zero HBM traffic for the recurrent state, exactly the
``lstm_scan`` policy applied to the SSM family (mamba2-130m, hymba-1.5b).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,       # (L, P)
    dt_ref,      # (L, 1)  fp32
    alpha_ref,   # (L, 1)  fp32 = dt * A  (negative decay logs)
    b_ref,       # (L, N)
    c_ref,       # (L, N)
    s0_ref,      # (P, N)  fp32 initial state
    y_ref,       # out (L, P)
    sf_ref,      # out (P, N) fp32 final state
    s_scr,       # VMEM scratch (P, N) fp32
):
    chunk = pl.program_id(1)
    n_chunks = pl.num_programs(1)

    @pl.when(chunk == 0)
    def _init():
        s_scr[...] = s0_ref[...].astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)          # (L, P)
    dt = dt_ref[...]                            # (L, 1)
    alpha = alpha_ref[...]                      # (L, 1)
    bmat = b_ref[...].astype(jnp.float32)       # (L, N)
    cmat = c_ref[...].astype(jnp.float32)       # (L, N)
    s_prev = s_scr[...]                         # (P, N)

    cum = jnp.cumsum(alpha, axis=0)             # (L, 1) inclusive
    l_len = x.shape[0]

    # ---- intra-chunk (parallel part) --------------------------------------
    # M[t, s] = exp(cum_t - cum_s) * dt_s * (C_t . B_s)   for s <= t
    rel = cum - jnp.swapaxes(cum, 0, 1)                       # (L, L)
    row = jax.lax.broadcasted_iota(jnp.int32, (l_len, l_len), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (l_len, l_len), 1)
    mask = row >= col
    decay = jnp.where(mask, jnp.exp(jnp.where(mask, rel, 0.0)), 0.0)
    scores = jnp.dot(cmat, jnp.swapaxes(bmat, 0, 1),
                     preferred_element_type=jnp.float32)      # (L, L)
    m = scores * decay * jnp.swapaxes(dt, 0, 1)               # dt_s on columns
    y = jnp.dot(m, x, preferred_element_type=jnp.float32)     # (L, P)

    # ---- inter-chunk (recurrent part) --------------------------------------
    y = y + jnp.dot(cmat * jnp.exp(cum), jnp.swapaxes(s_prev, 0, 1),
                    preferred_element_type=jnp.float32)       # (L, P)

    # ---- state carry --------------------------------------------------------
    total = cum[-1:, :]                                        # (1, 1)
    xw = x * dt * jnp.exp(total - cum)                         # (L, P)
    s_new = jnp.exp(total) * s_prev + jnp.dot(
        jnp.swapaxes(xw, 0, 1), bmat, preferred_element_type=jnp.float32
    )
    s_scr[...] = s_new
    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(chunk == n_chunks - 1)
    def _final():
        sf_ref[...] = s_new


def ssd_scan(
    x: jax.Array,      # (BH, T, P)
    dt: jax.Array,     # (BH, T) fp32
    alpha: jax.Array,  # (BH, T) fp32
    b: jax.Array,      # (BH, T, N)
    c: jax.Array,      # (BH, T, N)
    s0: jax.Array,     # (BH, P, N) fp32
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y: (BH, T, P), s_final: (BH, P, N) fp32). T % chunk == 0."""
    bh, t_len, p = x.shape
    n = b.shape[-1]
    assert t_len % chunk == 0, (t_len, chunk)
    n_chunks = t_len // chunk

    grid = (bh, n_chunks)
    in_specs = [
        pl.BlockSpec((None, chunk, p), lambda i, k: (i, k, 0)),
        pl.BlockSpec((None, chunk, 1), lambda i, k: (i, k, 0)),
        pl.BlockSpec((None, chunk, 1), lambda i, k: (i, k, 0)),
        pl.BlockSpec((None, chunk, n), lambda i, k: (i, k, 0)),
        pl.BlockSpec((None, chunk, n), lambda i, k: (i, k, 0)),
        pl.BlockSpec((None, p, n), lambda i, k: (i, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((None, chunk, p), lambda i, k: (i, k, 0)),
        pl.BlockSpec((None, p, n), lambda i, k: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bh, t_len, p), x.dtype),
        jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
    ]
    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(
        x,
        dt[..., None].astype(jnp.float32),
        alpha[..., None].astype(jnp.float32),
        b,
        c,
        s0.astype(jnp.float32),
    )
