"""Sequential Mamba-2 / attention hybrid (granite-4.0-h-micro).

Each layer holds ONE mixer, picked by ``cfg.layer_types``: a Mamba-2 block
(``ssm.ssm_block``: conv with bias, SSD at ``cfg.ssm_chunk``, D skip, gated
RMSNorm after ``y * silu(z)``) or causal GQA attention with no position
embedding (NoPE) and scores scaled by ``cfg.attention_multiplier``.  Every
mixer is followed by a SwiGLU MLP.  With the muP multipliers:

    x = embed[tokens] * embedding_multiplier
    per layer:  x = x + r * mixer(rms_norm(x))
                x = x + r * mlp(rms_norm(x))        r = residual_multiplier
    logits = rms_norm(x) @ embed.T / logits_scaling      (tied embedding)

Layers of one kind are stacked (``params["mamba"]``, ``params["attention"]``
with a leading layer axis).  The pattern repeats with a period (granite:
5 Mamba, 1 attention, 4 Mamba); the program loops over periods and, inside
one, over each run of layers of one kind, so 40 layers are 3 loop bodies
and not 40 unrolled layers.

The state of one sequence lives in a *slot* of a pool that holds S of them
side by side, both kinds of state in one pytree:

    ssd  (nM, S, H, P, N) fp32     per Mamba layer: the SSD state
    conv (nM, S, K-1, C)  fp32     per Mamba layer: the conv window
    k, v (nA, S, Hkv, D, cap)      per attention layer: the KV cache
    len  (S,) int32                tokens held in the slot

The KV cache keeps positions on the minor axis: a head width of 64 there
would fill half of each 128-lane tile, and the decode step's products
contract over D (scores) and over positions (values) in this layout as it
lies.

``prefill_states`` runs a right-padded prompt and returns the state it
leaves (padding leaves it exactly as the unpadded prompt would);
``write_slot`` puts that state into one slot; ``decode`` advances every
slot by one token at its own length.  ``init_cache`` / ``prefill`` /
``decode_step`` are the lock-step API of ``models.api`` on the same pool.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import ssm
from repro.models.layers import NO_SHARD, ShardCtx

MAMBA, ATTN = "mamba", "attention"


# ---------------------------------------------------------------------------
# the layer pattern
# ---------------------------------------------------------------------------

def pattern(cfg: ArchConfig):
    """(periods, per-period count of each kind, runs): the runs of one
    period as ``(kind, index of its first layer among the period's layers
    of that kind, count)``."""
    types = tuple(cfg.layer_types)
    n = len(types)
    if n != cfg.n_layers or set(types) - {MAMBA, ATTN}:
        raise ValueError(f"layer_types must name {cfg.n_layers} layers of "
                         f"{MAMBA!r} or {ATTN!r}: {types}")
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and types == types[:p] * (n // p))
    per = {MAMBA: 0, ATTN: 0}
    runs = []
    for kind, group in itertools.groupby(types[:period]):
        count = len(list(group))
        runs.append((kind, per[kind], count))
        per[kind] += count
    return n // period, per, tuple(runs)


def n_of(cfg: ArchConfig, kind: str) -> int:
    return cfg.layer_types.count(kind)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, kind: str) -> dict:
    km, kf = jax.random.split(key)
    mixer = (ssm.init_ssm_block(km, cfg) if kind == MAMBA
             else L.init_attention(km, cfg))
    return {
        "ln": jnp.ones((cfg.d_model,), jnp.float32),
        "mixer": mixer,
        "ln_mlp": jnp.ones((cfg.d_model,), jnp.float32),
        "mlp": L.init_mlp(kf, cfg.d_model, cfg.d_ff, cfg.dtype),
    }


def init_params(key, cfg: ArchConfig) -> dict:
    ke, km, ka = jax.random.split(key, 3)

    def stack(k, kind):
        keys = jax.random.split(k, n_of(cfg, kind))
        return jax.vmap(lambda kk: _init_layer(kk, cfg, kind))(keys)

    return {
        "embed": L.embed_init(ke, cfg.padded_vocab, cfg.d_model, cfg.dtype),
        MAMBA: stack(km, MAMBA),
        ATTN: stack(ka, ATTN),
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
    }


# ---------------------------------------------------------------------------
# the trunk: every layer, in pattern order
# ---------------------------------------------------------------------------

def _layers(params, cfg: ArchConfig, carry, mamba_fn, attn_fn):
    """Fold ``carry`` through every layer: ``fn(index among its kind,
    layer params, carry) -> carry``."""
    periods, per, runs = pattern(cfg)
    fns = {MAMBA: mamba_fn, ATTN: attn_fn}

    def period(i, c):
        for kind, first, count in runs:
            def layer(j, c, kind=kind, first=first):
                idx = i * per[kind] + first + j
                lp = jax.tree_util.tree_map(lambda a: a[idx], params[kind])
                return fns[kind](idx, lp, c)

            c = jax.lax.fori_loop(0, count, layer, c)
        return c

    return jax.lax.fori_loop(0, periods, period, carry)


def _residual(cfg: ArchConfig, x, h):
    return x + (h * cfg.residual_multiplier).astype(x.dtype)


def _mlp_half(cfg: ArchConfig, lp, x):
    return _residual(cfg, x, L.mlp(lp["mlp"], L.rms_norm(x, lp["ln_mlp"], cfg.norm_eps)))


def _embed(params, tokens, cfg: ArchConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    return (x * cfg.embedding_multiplier).astype(x.dtype)


def logits_of(params, x, cfg: ArchConfig) -> jax.Array:
    """Final norm, tied head, divided by ``logits_scaling``; fp32."""
    xn = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    out = jnp.einsum("...d,vd->...v", xn, params["embed"],
                     preferred_element_type=jnp.float32)
    return out / cfg.logits_scaling


def _trunk(params, x, cfg: ArchConfig, lengths=None, states=None):
    """Every layer over ``x`` (B, T, d).  ``states``, where given, holds
    zeroed per-sequence states ({"ssd", "conv"} stacked over Mamba layers,
    {"k", "v"} (nA, B, Hkv, D, T)) that each layer fills in."""

    def mamba(idx, lp, c):
        x, st = c
        xn = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        h, new = ssm.ssm_block(lp["mixer"], xn, cfg, chunk=cfg.ssm_chunk,
                               lengths=lengths)
        x = _mlp_half(cfg, lp, _residual(cfg, x, h))
        if st is not None:
            st = dict(st, ssd=st["ssd"].at[idx].set(new["ssd"]),
                      conv=st["conv"].at[idx].set(new["conv"]))
        return x, st

    def attn(idx, lp, c):
        x, st = c
        b, t, _ = x.shape
        xn = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        q, k, v = L._proj_qkv(lp["mixer"], xn, xn, cfg)
        # causal: a real position never sees the padding after it
        out = L.sdpa(q, k, v, causal=True, scale=cfg.attention_multiplier)
        h = out.reshape(b, t, cfg.n_heads * cfg.hd) @ lp["mixer"]["wo"]
        x = _mlp_half(cfg, lp, _residual(cfg, x, h))
        if st is not None:
            if lengths is not None:  # padded positions hold no keys
                real = (jnp.arange(t)[None, :] < lengths[:, None])[..., None, None]
                k, v = jnp.where(real, k, 0), jnp.where(real, v, 0)
            st = dict(st, **{
                name: st[name].at[idx].set(
                    a.transpose(0, 2, 3, 1).astype(st[name].dtype))
                for name, a in (("k", k), ("v", v))})
        return x, st

    return _layers(params, cfg, (x, states), mamba, attn)


def forward(params, batch, cfg: ArchConfig, ctx: ShardCtx = NO_SHARD,
            remat: bool = True) -> jax.Array:
    """Full-sequence causal LM forward -> logits (B, T, V) fp32."""
    del ctx, remat
    x, _ = _trunk(params, _embed(params, batch["tokens"], cfg), cfg)
    return logits_of(params, x, cfg)


def loss_fn(params, batch, cfg: ArchConfig, ctx: ShardCtx = NO_SHARD):
    return L.softmax_xent(forward(params, batch, cfg, ctx), batch["labels"], cfg.vocab)


# ---------------------------------------------------------------------------
# state: prefill, the slot pool, decode
# ---------------------------------------------------------------------------

def _seq_states(cfg: ArchConfig, batch: int, t_len: int, kv_dtype) -> dict:
    nm, na = n_of(cfg, MAMBA), n_of(cfg, ATTN)
    one = ssm.init_ssm_state(cfg, batch)
    kv = (na, batch, cfg.n_kv_heads, cfg.hd, t_len)
    return {
        "ssd": jnp.zeros((nm, *one["ssd"].shape), jnp.float32),
        "conv": jnp.zeros((nm, *one["conv"].shape), jnp.float32),
        "k": jnp.zeros(kv, kv_dtype), "v": jnp.zeros(kv, kv_dtype),
    }


def prefill_states(params, tokens, lengths, cfg: ArchConfig):
    """Right-padded prompts ``tokens`` (B, T) holding ``lengths`` (B,) real
    tokens -> (logits at each row's last real token (B, V) fp32, the state
    they leave: {"ssd", "conv", "k", "v"} with KV (nA, B, Hkv, D, T), zero
    on padded positions)."""
    b, t = tokens.shape
    states = _seq_states(cfg, b, t, cfg.dtype)
    x, states = _trunk(params, _embed(params, tokens, cfg), cfg, lengths, states)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return logits_of(params, last, cfg), states


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None) -> dict:
    """A pool of ``batch`` empty slots of ``max_len`` tokens each."""
    pool = _seq_states(cfg, batch, max_len, dtype or cfg.dtype)
    pool["len"] = jnp.zeros((batch,), jnp.int32)
    return pool


def write_slot(pool: dict, states: dict, slot, length) -> dict:
    """``pool`` with slot ``slot`` holding one prefilled sequence
    (``states`` of batch 1, ``length`` tokens); KV past the prompt, in the
    padding and up to the slot's capacity, is zero."""
    cap = pool["k"].shape[-1]

    def kv(a):
        a = a[..., :cap]
        return jnp.pad(a, ((0, 0),) * 4 + ((0, cap - a.shape[-1]),))

    new = dict(pool)
    for name, a in (("ssd", states["ssd"]), ("conv", states["conv"]),
                    ("k", kv(states["k"])), ("v", kv(states["v"]))):
        start = (0, slot) + (0,) * (a.ndim - 2)
        new[name] = jax.lax.dynamic_update_slice(pool[name], a.astype(pool[name].dtype), start)
    new["len"] = pool["len"].at[slot].set(length)
    return new


def decode(params, pool: dict, tokens, cfg: ArchConfig):
    """One token for every slot: ``tokens`` (S,) enter at each slot's own
    length -> (logits (S, V) fp32, the pool one token longer).  A slot at
    capacity overwrites its last position (its output is not used)."""
    s = tokens.shape[0]
    cap = pool["k"].shape[-1]
    pos = jnp.minimum(pool["len"], cap - 1)            # where the token goes
    rows = jnp.arange(s)
    seen = (jnp.arange(cap)[None, :] <= pos[:, None])[:, None, None, :]
    g = cfg.n_heads // cfg.n_kv_heads

    def mamba(idx, lp, c):
        x, st = c
        xn = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        h, new = ssm.ssm_block_decode(
            lp["mixer"], xn, {"conv": st["conv"][idx], "ssd": st["ssd"][idx]}, cfg)
        st = dict(st, ssd=st["ssd"].at[idx].set(new["ssd"]),
                  conv=st["conv"].at[idx].set(new["conv"]))
        return _mlp_half(cfg, lp, _residual(cfg, x, h)), st

    def attn(idx, lp, c):
        x, st = c
        xn = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        q, k, v = L._proj_qkv(lp["mixer"], xn, xn, cfg)
        kc = st["k"].at[idx, rows, :, :, pos].set(k[:, 0].astype(st["k"].dtype))
        vc = st["v"].at[idx, rows, :, :, pos].set(v[:, 0].astype(st["v"].dtype))
        qg = q.reshape(s, cfg.n_kv_heads, g, cfg.hd)
        scores = jnp.einsum("shgd,shdk->shgk", qg, kc[idx],
                            preferred_element_type=jnp.float32)
        w = jax.nn.softmax(
            jnp.where(seen, scores * cfg.attention_multiplier, -1e30), axis=-1)
        out = jnp.einsum("shgk,shdk->shgd", w.astype(vc.dtype), vc[idx],
                         preferred_element_type=jnp.float32)
        h = out.reshape(s, 1, cfg.n_heads * cfg.hd).astype(x.dtype) @ lp["mixer"]["wo"]
        return _mlp_half(cfg, lp, _residual(cfg, x, h)), dict(st, k=kc, v=vc)

    x = _embed(params, tokens, cfg)[:, None, :]
    x, pool = _layers(params, cfg, (x, pool), mamba, attn)
    pool = dict(pool, len=jnp.minimum(pool["len"] + 1, cap))
    return logits_of(params, x[:, 0], cfg), pool


def prefill(params, batch, cfg: ArchConfig, max_len=None, ctx: ShardCtx = NO_SHARD):
    """Lock-step API: every row a whole prompt; a pool of capacity
    ``max_len`` with slot ``i`` holding row ``i``."""
    del ctx
    tokens = batch["tokens"]
    b, t = tokens.shape
    lengths = jnp.full((b,), t, jnp.int32)
    logits, states = prefill_states(params, tokens, lengths, cfg)
    pad = ((0, 0),) * 4 + ((0, max(max_len or t, t) - t),)
    pool = dict(states, k=jnp.pad(states["k"], pad), v=jnp.pad(states["v"], pad),
                len=lengths)
    return logits[:, None], pool


def decode_step(params, cache, batch, cfg: ArchConfig, ctx: ShardCtx = NO_SHARD):
    del ctx
    logits, pool = decode(params, cache, batch["tokens"][:, 0], cfg)
    return logits[:, None], pool
