"""Single-query (decode) flash attention over a long KV cache — Pallas TPU.

One new token attends to a cache of S past keys/values (decode_32k: S=32768;
long_500k: S=524288, batch 1).  The cache is streamed through VMEM in blocks
of ``block_s`` with an online-softmax accumulator resident in VMEM scratch —
the same "recurrent state never leaves VMEM" policy as ``lstm_scan``, here
applied to the (m, l, acc) softmax state instead of (h, c).

GQA layout: q has Hq heads, the cache has Hkv heads, G = Hq/Hkv query heads
share each cache head.  The kernel loops over the (static, small) Hkv heads
and does one (G, D) x (D, Sb) MXU matmul per cache head per block.

Grid = (B, S/block_s): batch parallel, cache blocks sequential ("arbitrary").
Valid-length masking reads per-batch lengths from SMEM, so padded cache tail
blocks contribute exp(-inf) = 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _decode_attn_kernel(
    len_ref,   # SMEM (1,) int32 — valid cache length for this batch row
    q_ref,     # (Hq, D)
    k_ref,     # (Sb, Hkv, D)
    v_ref,     # (Sb, Hkv, D)
    o_ref,     # out (Hq, D)
    m_scr,     # VMEM (Hq, 1) fp32 running max
    l_scr,     # VMEM (Hq, 1) fp32 running denominator
    acc_scr,   # VMEM (Hq, D) fp32 running numerator
    *,
    n_kv_heads: int,
    scale: float,
):
    s_blk = pl.program_id(1)
    n_blk = pl.num_programs(1)
    sb = k_ref.shape[0]
    hq, d = q_ref.shape
    g = hq // n_kv_heads

    @pl.when(s_blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = s_blk * sb + jax.lax.broadcasted_iota(jnp.int32, (1, sb), 1)
    valid = pos < len_ref[0]                                   # (1, Sb)

    q = q_ref[...].astype(jnp.float32) * scale                 # (Hq, D)

    # scores for all q heads against their GQA cache head -> (Hq, Sb)
    rows = []
    for h in range(n_kv_heads):
        q_h = q[h * g : (h + 1) * g, :]                        # (G, D)
        k_h = k_ref[:, h, :].astype(jnp.float32)               # (Sb, D)
        rows.append(
            jnp.dot(q_h, jnp.swapaxes(k_h, 0, 1),
                    preferred_element_type=jnp.float32)        # (G, Sb)
        )
    scores = jnp.concatenate(rows, axis=0)                     # (Hq, Sb)
    scores = jnp.where(valid, scores, _NEG_INF)

    # ---- online softmax update -------------------------------------------
    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    p = jnp.exp(scores - m_new)                                # (Hq, Sb)
    corr = jnp.exp(m_prev - m_new)                             # (Hq, 1)
    l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)

    outs = []
    for h in range(n_kv_heads):
        p_h = p[h * g : (h + 1) * g, :]                        # (G, Sb)
        v_h = v_ref[:, h, :].astype(jnp.float32)               # (Sb, D)
        outs.append(jnp.dot(p_h, v_h, preferred_element_type=jnp.float32))
    pv = jnp.concatenate(outs, axis=0)                         # (Hq, D)
    acc_new = corr * acc_prev + pv

    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(s_blk == n_blk - 1)
    def _final():
        o_ref[...] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


def decode_attn(
    q: jax.Array,        # (B, Hq, D)
    k: jax.Array,        # (B, S, Hkv, D)
    v: jax.Array,        # (B, S, Hkv, D)
    lengths: jax.Array,  # (B,) int32 valid cache lengths
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns attention output (B, Hq, D). S must be a block_s multiple."""
    batch, hq, d = q.shape
    _, s_len, hkv, _ = k.shape
    assert s_len % block_s == 0, (s_len, block_s)
    assert hq % hkv == 0, (hq, hkv)
    scale = 1.0 / (d**0.5)

    kernel = functools.partial(
        _decode_attn_kernel, n_kv_heads=hkv, scale=scale
    )
    grid = (batch, s_len // block_s)
    in_specs = [
        pl.BlockSpec((1,), lambda b, s: (b,), memory_space=pltpu.SMEM),
        pl.BlockSpec((None, hq, d), lambda b, s: (b, 0, 0)),
        pl.BlockSpec((None, block_s, hkv, d), lambda b, s: (b, s, 0, 0)),
        pl.BlockSpec((None, block_s, hkv, d), lambda b, s: (b, s, 0, 0)),
    ]
    out_specs = pl.BlockSpec((None, hq, d), lambda b, s: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct((batch, hq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attn",
    )(lengths.astype(jnp.int32), q, k, v)
