"""Mamba-2 (SSD) blocks — mamba2-130m, and the SSM branch of hymba.

The block follows the Mamba-2 structure: one fused input projection to
(z | x | B | C | dt), a short causal depthwise conv over (x|B|C), softplus
dt, the SSD scan (scalar decay per head), D skip, silu(z) gating, RMSNorm,
output projection.

Two scan execution paths, both matching kernels/ssd_scan/ref.py:
  * ``ssd_chunked`` — pure-jnp chunked scan (lax.scan over chunks, MXU
    matmuls inside).  Used for train/prefill and for the dry-run lowering
    (the paper's mvm_x/recurrent split: intra-chunk work is the parallel
    sub-layer, the inter-chunk state carry is the dependency-bound one).
  * ``kernels/ssd_scan`` — the fused Pallas kernel (TPU runtime path).

Decode keeps O(1) state per token: (conv window, SSD state) — this is why
the SSM archs run the long_500k cell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.layers import NO_SHARD, ShardCtx


# ---------------------------------------------------------------------------
# chunked SSD in pure jnp (vectorized over batch and heads)
# ---------------------------------------------------------------------------

def ssd_chunked(
    x: jax.Array,      # (B, T, H, P)
    dt: jax.Array,     # (B, T, H) fp32
    a: jax.Array,      # (H,) negative decay rates
    bm: jax.Array,     # (B, T, G, N)
    cm: jax.Array,     # (B, T, G, N)
    s0: jax.Array | None = None,
    chunk: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,T,H,P), final state (B,H,P,N) fp32)."""
    batch, t_len, heads, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    rep = heads // groups
    chunk = min(chunk, max(t_len, 1))
    pad = (-t_len) % chunk
    if pad:  # zero dt => exact no-op steps
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (t_len + pad) // chunk

    bm_h = jnp.repeat(bm, rep, axis=2).astype(jnp.float32)   # (B,T,H,N)
    cm_h = jnp.repeat(cm, rep, axis=2).astype(jnp.float32)
    alpha = (dt * a[None, None, :]).astype(jnp.float32)      # (B,T,H)

    def to_chunks(v):
        return jnp.moveaxis(
            v.reshape(batch, n_chunks, chunk, *v.shape[2:]), 1, 0
        )  # (n_chunks, B, chunk, ...)

    xs = (
        to_chunks(x.astype(jnp.float32)),
        to_chunks(dt.astype(jnp.float32)),
        to_chunks(alpha),
        to_chunks(bm_h),
        to_chunks(cm_h),
    )
    if s0 is None:
        s0 = jnp.zeros((batch, heads, p, n), jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = row >= col

    def chunk_step(s_prev, inp):
        xc, dtc, alc, bc, cc = inp     # (B,L,H,P) (B,L,H) (B,L,H) (B,L,H,N)
        cum = jnp.cumsum(alc, axis=1)  # (B,L,H)
        rel = cum[:, :, None, :] - cum[:, None, :, :]          # (B,L,L,H)
        decay = jnp.where(tril[None, :, :, None],
                          jnp.exp(jnp.where(tril[None, :, :, None], rel, 0.0)), 0.0)
        scores = jnp.einsum("blhn,bshn->blsh", cc, bc)         # (B,L,L,H)
        m = scores * decay * dtc[:, None, :, :]                # dt_s on col s
        y = jnp.einsum("blsh,bshp->blhp", m, xc)               # intra-chunk
        y = y + jnp.einsum(                                    # inter-chunk
            "blhn,bhpn,blh->blhp", cc, s_prev, jnp.exp(cum)
        )
        total = cum[:, -1, :]                                  # (B,H)
        xw = xc * (dtc * jnp.exp(total[:, None, :] - cum))[..., None]
        s_new = jnp.exp(total)[:, :, None, None] * s_prev + jnp.einsum(
            "bshp,bshn->bhpn", xw, bc
        )
        return s_new, y

    # remat: per-chunk (L x L) decay/score tensors are recomputed in the
    # backward pass instead of being stacked across all chunks (the
    # dominant SSM train-memory term)
    s_f, ys = jax.lax.scan(jax.checkpoint(chunk_step), s0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(batch, t_len + pad, heads, p)[:, :t_len]
    return y.astype(x.dtype), s_f


# ---------------------------------------------------------------------------
# depthwise causal conv (width K, shift-add form)
# ---------------------------------------------------------------------------

def causal_conv(x: jax.Array, w: jax.Array, state: jax.Array | None = None,
                bias: jax.Array | None = None):
    """x (B,T,Ch), w (Ch,K) -> (B,T,Ch). state (B,K-1,Ch) prepends history;
    ``bias`` (Ch,) is added before the result is cast to x's dtype."""
    k = w.shape[1]
    if state is None:
        x_pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        x_pad = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(
        x_pad[:, i : i + x.shape[1], :] * w[None, None, :, k - 1 - i]
        for i in range(k)
    )
    if bias is not None:
        out = out + bias
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# block
# ---------------------------------------------------------------------------

def _dims(cfg: ArchConfig, hybrid_branch: bool):
    d_inner = cfg.d_model if hybrid_branch else cfg.ssm_expand * cfg.d_model
    heads = d_inner // cfg.ssm_head_dim
    gn = cfg.ssm_groups * cfg.ssm_state
    conv_ch = d_inner + 2 * gn
    return d_inner, heads, gn, conv_ch


def init_ssm_block(key, cfg: ArchConfig, hybrid_branch: bool = False) -> dict:
    d_inner, heads, gn, conv_ch = _dims(cfg, hybrid_branch)
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_inner + 2 * gn + heads  # z | x | B | C | dt
    p = {
        "in_proj": L.dense_init(ks[0], cfg.d_model, proj_out, cfg.dtype),
        "conv_w": (jax.random.normal(ks[1], (conv_ch, cfg.conv_kernel), jnp.float32) * 0.2),
        "a_log": jnp.zeros((heads,), jnp.float32),        # A = -exp(a_log) = -1
        "d_skip": jnp.ones((heads,), jnp.float32),
        "dt_bias": jnp.zeros((heads,), jnp.float32),
        "norm": jnp.ones((d_inner,), jnp.float32),
        "out_proj": L.dense_init(ks[2], d_inner, cfg.d_model, cfg.dtype),
    }
    if cfg.conv_bias:
        p["conv_b"] = jax.random.normal(ks[3], (conv_ch,), jnp.float32) * 0.1
    return p


def _split_proj(p, u, cfg: ArchConfig, hybrid_branch: bool):
    d_inner, heads, gn, _ = _dims(cfg, hybrid_branch)
    z, xbc, dt_raw = jnp.split(u, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    return z, xbc, dt_raw, (d_inner, heads, gn)


def ssm_block(
    p: dict, x_in: jax.Array, cfg: ArchConfig,
    hybrid_branch: bool = False, chunk: int = 64,
    state: dict | None = None, lengths: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Full-sequence SSM block. Returns (out (B,T,d), final decode state).

    ``lengths`` (B,): each row holds that many real steps, then padding.
    A padded step has dt = 0, which leaves the SSD state exactly as it was,
    and the conv window is read at the row's length, so the state returned
    is the unpadded sequence's."""
    b, t, _ = x_in.shape
    u = x_in @ p["in_proj"]
    z, xbc, dt_raw, (d_inner, heads, gn) = _split_proj(p, u, cfg, hybrid_branch)
    conv_state_in = None if state is None else state["conv"]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], conv_state_in, p.get("conv_b")))
    xs, bm, cm = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    n, g = cfg.ssm_state, cfg.ssm_groups
    xh = xs.reshape(b, t, heads, cfg.ssm_head_dim)
    bm = bm.reshape(b, t, g, n)
    cm = cm.reshape(b, t, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    if lengths is not None:
        real = jnp.arange(t)[None, :] < lengths[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
    a = -jnp.exp(p["a_log"])
    s0 = None if state is None else state["ssd"]
    y, s_f = ssd_chunked(xh, dt, a, bm, cm, s0=s0, chunk=chunk)
    y = y + p["d_skip"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(b, t, d_inner).astype(x_in.dtype)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["out_proj"]
    k = cfg.conv_kernel
    xbc_raw = jnp.split(u, [d_inner, 2 * d_inner + 2 * gn], axis=-1)[1]
    if state is not None:
        hist = jnp.concatenate([state["conv"].astype(xbc_raw.dtype), xbc_raw], axis=1)
    else:
        hist = jnp.pad(xbc_raw, ((0, 0), (k - 1, 0), (0, 0)))
    if lengths is None:
        window = hist[:, -(k - 1):, :]
    else:  # the k-1 inputs before each row's length: hist[len : len+k-1]
        window = jax.vmap(
            lambda h, n: jax.lax.dynamic_slice_in_dim(h, n, k - 1, axis=0)
        )(hist, lengths)
    new_state = {"conv": window.astype(jnp.float32), "ssd": s_f}
    return out, new_state


def ssm_block_decode(
    p: dict, x_in: jax.Array, state: dict, cfg: ArchConfig,
    hybrid_branch: bool = False,
) -> tuple[jax.Array, dict]:
    """One-token decode: O(1) update of (conv window, SSD state)."""
    from repro.kernels.ssd_scan import ssd_decode_step

    b = x_in.shape[0]
    u = x_in @ p["in_proj"]                       # (B, 1, proj)
    z, xbc, dt_raw, (d_inner, heads, gn) = _split_proj(p, u, cfg, hybrid_branch)
    conv_in = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], axis=1)
    k = cfg.conv_kernel
    # the prefill's own conv arithmetic, over the window and the new input
    xbc_c = jax.nn.silu(
        causal_conv(xbc, p["conv_w"], state["conv"], p.get("conv_b")))
    xs, bm, cm = jnp.split(xbc_c, [d_inner, d_inner + gn], axis=-1)
    n, g = cfg.ssm_state, cfg.ssm_groups
    xh = xs.reshape(b, heads, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    y, s_new = ssd_decode_step(
        xh.astype(jnp.float32), dt, a,
        bm.reshape(b, g, n).astype(jnp.float32),
        cm.reshape(b, g, n).astype(jnp.float32),
        state["ssd"],
    )
    y = y + p["d_skip"][None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(b, 1, d_inner).astype(x_in.dtype)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["out_proj"]
    new_conv = conv_in[:, -(k - 1):, :].astype(jnp.float32)
    return out, {"conv": new_conv, "ssd": s_new}


def init_ssm_state(cfg: ArchConfig, batch: int, hybrid_branch: bool = False) -> dict:
    d_inner, heads, gn, conv_ch = _dims(cfg, hybrid_branch)
    return {
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, conv_ch), jnp.float32),
        "ssd": jnp.zeros((batch, heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
    }


# ---------------------------------------------------------------------------
# full mamba2 model (attention-free)
# ---------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    stacked = jax.vmap(
        lambda k: {
            "ssm": init_ssm_block(k, cfg),
            "ln": jnp.ones((cfg.d_model,), jnp.float32),
        }
    )(jax.random.split(kl, cfg.n_layers))
    return {
        "embed": L.embed_init(ke, cfg.padded_vocab, cfg.d_model, cfg.dtype),
        "layers": stacked,
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": L.dense_init(kh, cfg.d_model, cfg.padded_vocab, cfg.dtype),
    }


def forward(params, batch, cfg: ArchConfig, ctx: ShardCtx = NO_SHARD, remat=True):
    x = jnp.take(params["embed"], batch["tokens"], axis=0)

    def body(x, lp):
        h, _ = ssm_block(lp["ssm"], L.rms_norm(x, lp["ln"], cfg.norm_eps), cfg)
        return L.constrain_residual(x + h, ctx)

    if remat:
        body = jax.checkpoint(body)

    def scan_fn(x, lp):
        return body(x, lp), None

    x, _ = jax.lax.scan(scan_fn, x, params["layers"])
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x @ params["lm_head"]


def loss_fn(params, batch, cfg: ArchConfig, ctx: ShardCtx = NO_SHARD):
    return L.softmax_xent(forward(params, batch, cfg, ctx), batch["labels"], cfg.vocab)


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None) -> dict:
    """SSM 'cache' = per-layer (conv, ssd) state; O(1) in sequence length."""
    one = init_ssm_state(cfg, batch)
    return {
        "state": jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers, *a.shape)), one
        ),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(params, batch, cfg: ArchConfig, max_len=None, ctx: ShardCtx = NO_SHARD):
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    s = x.shape[1]

    def scan_fn(x, lp):
        h, st = ssm_block(lp["ssm"], L.rms_norm(x, lp["ln"], cfg.norm_eps), cfg)
        return x + h, st

    x, states = jax.lax.scan(scan_fn, x, params["layers"])
    x = L.rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
    return x @ params["lm_head"], {"state": states, "pos": jnp.asarray(s, jnp.int32)}


def decode_step(params, cache, batch, cfg: ArchConfig, ctx: ShardCtx = NO_SHARD):
    x = jnp.take(params["embed"], batch["tokens"], axis=0)

    def scan_fn(x, inp):
        lp, st = inp
        h, st = ssm_block_decode(lp["ssm"], L.rms_norm(x, lp["ln"], cfg.norm_eps), st, cfg)
        return x + h, st

    x, states = jax.lax.scan(scan_fn, x, (params["layers"], cache["state"]))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x @ params["lm_head"], {"state": states, "pos": cache["pos"] + 1}
