"""Shared model building blocks: norms, RoPE, GQA attention, MLPs, embeddings.

Functional style: parameters are plain pytrees created by ``init_*`` functions
(so the dry-run can build them under ``jax.eval_shape`` with zero allocation),
forward functions are pure.  Sharding is applied from the outside via
parameter/input NamedShardings (GSPMD propagates internals); optional
activation constraints are threaded through ``ShardCtx``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


@dataclass(frozen=True)
class ShardCtx:
    """Optional activation-sharding context (mesh + axis names).

    ``residual``: how the carried (B, S, d) residual stream is sharded over
    the model axis between layers —
      "d"   : feature-sharded (Megatron-SP style; gathers d per layer)
      "seq" : sequence-sharded (Ulysses style; MLP/norms are token-local,
              attention reshards seq<->heads via all-to-all)
    """

    mesh: Any = None
    data_axes: tuple = ("data",)   # ("pod","data") on the multi-pod mesh
    model_axis: str | None = "model"  # None: no tensor parallelism (dp_all)
    residual: str = "d"

    def constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    @property
    def batch_spec(self):
        return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]


NO_SHARD = ShardCtx()


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, n_in: int, n_out: int, dtype) -> jax.Array:
    scale = (1.0 / n_in) ** 0.5
    return (jax.random.normal(key, (n_in, n_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with f32-accumulated statistics but NO materialized f32 copy
    of x.  A plain ``x.astype(f32)`` upcast becomes an AD residual whose
    full per-layer stack XLA then hoists out of the backward scan in f32 —
    2x the remat-stack memory for nothing (observed on the dry-run; see
    EXPERIMENTS.md §Perf).  The einsum accumulates x*x in f32 directly from
    bf16 inputs (exactly the MXU/VPU accumulation behaviour), and the
    normalization is applied in the input dtype.
    """
    ms = jnp.einsum(
        "...d,...d->...", x, x, preferred_element_type=jnp.float32
    ) / x.shape[-1]
    inv = jax.lax.rsqrt(ms + eps)[..., None].astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_tables(positions: jax.Array, head_dim: int, theta: float):
    """cos/sin tables for given integer positions: (..., head_dim/2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (S, D/2) or (B, S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig, cross: bool = False) -> dict:
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, cfg.dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, cfg.dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, cfg.dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, cfg.dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
    return p


def _proj_qkv(p: dict, x: jax.Array, x_kv: jax.Array, cfg: ArchConfig):
    b, s = x.shape[:2]
    s_kv = x_kv.shape[1]
    hd = cfg.hd
    q = x @ p["wq"]
    k = x_kv @ p["wk"]
    v = x_kv @ p["wv"]
    if "bq" in p:
        q = (q.astype(jnp.float32) + p["bq"]).astype(q.dtype)
        k = (k.astype(jnp.float32) + p["bk"]).astype(k.dtype)
        v = (v.astype(jnp.float32) + p["bv"]).astype(v.dtype)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s_kv, cfg.n_kv_heads, hd)
    v = v.reshape(b, s_kv, cfg.n_kv_heads, hd)
    return q, k, v


def sdpa(
    q: jax.Array,            # (B, Sq, Hq, D)
    k: jax.Array,            # (B, Sk, Hkv, D)
    v: jax.Array,            # (B, Sk, Hkv, D)
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,   # absolute position of q[0] (decode)
    kv_len: jax.Array | None = None,  # valid cache length (masks padded tail)
    window: int | None = None,        # sliding-window width (tokens back)
    scale: float | None = None,       # score scale (None: 1/sqrt(D))
) -> jax.Array:
    """Masked GQA scaled-dot-product attention (pure jnp; XLA fuses well).

    Returns (B, Sq, Hq, D).  GQA is computed by reshaping q heads into
    (Hkv, G) groups — no materialized repeat of K/V.
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    # f32 accumulation WITHOUT materializing f32 copies of K/V — a cast of
    # a seq-sharded 32k-entry cache would be gigabytes (and invites GSPMD
    # gathers); preferred_element_type gives MXU-style bf16xbf16->f32.
    qf = q.reshape(b, sq, hkv, g, d)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qf, k, preferred_element_type=jnp.float32
    )
    scores = scores / d**0.5 if scale is None else scores * scale

    q_pos = jnp.arange(sq) + q_offset          # (Sq,)
    k_pos = jnp.arange(sk)                     # (Sk,)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    mask = mask[None, None, None]
    if kv_len is not None:
        mask = mask & (k_pos[None, None, None, None, :] < kv_len)
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", w.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def attention(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    *,
    rope: tuple[jax.Array, jax.Array] | None,
    causal: bool = True,
    x_kv: jax.Array | None = None,    # cross-attention source
    window: int | None = None,
    ctx: ShardCtx = NO_SHARD,
) -> jax.Array:
    """Full-sequence attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = _proj_qkv(p, x, x_kv if x_kv is not None else x, cfg)
    if rope is not None and x_kv is None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = ctx.constrain(q, P(ctx.batch_spec, None, ctx.model_axis, None))
    if max(s, k.shape[1]) > 1024:  # blocked path: no (Sq x Sk) tensor
        from repro.models.flash_attention import flash_attention

        out = flash_attention(q, k, v, causal and x_kv is None, window, 0)
    else:
        out = sdpa(q, k, v, causal=causal and x_kv is None, window=window)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd)
    return out @ p["wo"]


def attention_decode(
    p: dict,
    x: jax.Array,                 # (B, 1, d)
    cache_k: jax.Array,           # (B, S_max, Hkv, D) — includes this token's slot
    cache_v: jax.Array,
    pos: jax.Array,               # scalar int32: index of the new token
    cfg: ArchConfig,
    *,
    window: int | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode: append to cache, attend over valid prefix.

    Returns (out (B,1,d), new_cache_k, new_cache_v).
    """
    b = x.shape[0]
    hd = cfg.hd
    q, k, v = _proj_qkv(p, x, x, cfg)
    cos, sin = rope_tables(pos[None], hd, cfg.rope_theta)  # (1, hd/2)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, axis=1)
    kv_len = pos + 1
    if window is not None:
        kv_len_lo = jnp.maximum(kv_len - window, 0)
    else:
        kv_len_lo = 0
    del kv_len_lo  # full-cache masked attention below handles the window
    if use_kernel:
        from repro.kernels.decode_attn import decode_attn_op

        lengths = jnp.full((b,), kv_len, jnp.int32)
        out = decode_attn_op(q[:, 0], cache_k, cache_v, lengths)[:, None]
    else:
        out = sdpa(
            q, cache_k, cache_v, causal=False, q_offset=pos,
            kv_len=kv_len, window=window,
        )
    out = out.reshape(b, 1, cfg.n_heads * hd)
    return out @ p["wo"], cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
        "w_up": dense_init(ks[1], d_model, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d_model, dtype),
    }


def mlp(p: dict, x: jax.Array, ctx: ShardCtx = NO_SHARD) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = ctx.constrain(h, P(ctx.batch_spec, None, ctx.model_axis))
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(
    logits: jax.Array, labels: jax.Array, valid_vocab: int | None = None
) -> jax.Array:
    """Mean next-token cross-entropy. logits (B,S,Vp) fp32-safe; labels (B,S).

    ``valid_vocab`` masks padded vocabulary columns (embeddings are padded
    to a shardable multiple; the pad must not receive probability mass).
    """
    logits = logits.astype(jnp.float32)
    if valid_vocab is not None and valid_vocab < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) < valid_vocab
        logits = jnp.where(mask, logits, -1e30)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def constrain_residual(x, ctx: "ShardCtx"):
    """Shard the carried residual stream (B, S, d) per ctx.residual."""
    import jax.sharding as _sh
    if ctx.residual == "seq":
        spec = _sh.PartitionSpec(ctx.batch_spec, ctx.model_axis, None)
    else:
        spec = _sh.PartitionSpec(ctx.batch_spec, None, ctx.model_axis)
    return ctx.constrain(x, spec)
