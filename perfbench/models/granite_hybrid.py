"""Plain reference of granite-4.0-h-micro, its weights and its work.

The architecture written out independently of the program under test
(nothing of ``src/`` is imported), for comparing the program with it.
A configuration is a dict of the published ``config.json`` keys
(``layer_types``, ``hidden_size``, ``mamba_*``, the muP multipliers, ...).

* ``init_params`` makes seeded bf16 weights (fp32 norms, conv and SSD
  vectors) on the device, laid out the way the serving engine takes them:
  ``{"embed", "ln_f", "mamba": {...}, "attention": {...}}`` with each
  kind's layers stacked on a leading axis;
* ``scores`` is the plain forward pass in float32 at HIGHEST precision,
  teacher-forced on prompt and emitted tokens, with no kernels, cache or
  batching of the work: the weights cast to fp32 one layer at a time, the
  SSD as its sequential recurrence, plain causal softmax attention.  It
  returns the probabilities of each emitted token and of the request's
  ids at every generated position.  ``control=True`` is the control the
  cell's limits hold out: every activation that enters a product rounded
  to fp8 e4m3, one precision below the configuration's bf16;
  ``control="bf16_state"`` rounds the SSD state to bf16 after every
  update instead, one precision below its fp32 (``CONTROLS``);
* ``logits`` is the same forward pass returning a small model's logits;
* ``step_work``, ``model_flops`` and the byte counts under them are the
  decode step's and the model's work at published widths, for the
  roofline and utilisation metrics.

The equations (granite-4.0-h, ``model_type`` granitemoehybrid):

    x = embed[tokens] * embedding_multiplier
    per layer:  x = x + r * mixer(rms_norm(x) * ln)
                x = x + r * mlp(rms_norm(x) * ln_mlp)     r = residual_multiplier
    mlp(h) = (silu(h W_gate) * (h W_up)) W_down
    logits = (rms_norm(x) * ln_f) @ embed.T / logits_scaling

Mamba-2 mixer: ``u = h W_in`` split into z (d_inner) | xBC (d_inner + 2 N)
| dt (H); a causal depthwise conv of width 4 with bias over xBC, then silu;
xBC split into x (H heads of P), B and C (one group of N);
``dt = softplus(dt + dt_bias)``, ``A = -exp(a_log)``; per head
``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``;
``out = (rms_norm(y * silu(z)) * norm) W_out``.  Attention: causal GQA,
no position embedding, scores ``q.k * attention_multiplier``.

Departures from the published description, all of layout and not of
mathematics: the conv weight is stored (channels, taps) with tap ``j``
multiplying the input ``j`` steps back (the reverse of a torch Conv1d
weight); the MLP's fused input projection is kept as two matrices
(gate, up); the weights are random.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MAMBA, ATTN = "mamba", "attention"
#: log-probabilities each request names besides its emitted token's
N_IDS = 16
#: the controls: every activation that enters a product rounded to fp8
#: e4m3, one precision below the configuration's bf16 (``control=True``,
#: the one the cell's limits hold out); the SSD state rounded to bf16
#: after every update, one precision below its fp32
FP8, BF16_STATE = "fp8_activations", "bf16_state"
CONTROLS = (FP8, BF16_STATE)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    gn = cfg["mamba_n_groups"] * n
    di = heads * p
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "d": d, "ff": cfg["shared_intermediate_size"], "V": cfg["vocab_size"],
        "H": heads, "P": p, "N": n, "G": cfg["mamba_n_groups"], "di": di,
        "C": di + 2 * gn, "K": cfg["mamba_d_conv"],
        "proj": 2 * di + 2 * gn + heads,
        "hq": hq, "hkv": hkv, "hd": d // hq,
        "nM": cfg["layer_types"].count(MAMBA),
        "nA": cfg["layer_types"].count(ATTN),
    }


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def shapes(cfg: dict) -> dict:
    """Every weight: (shape, dtype, how it is drawn)."""
    m = dims(cfg)
    d, ff, nM, nA = m["d"], m["ff"], m["nM"], m["nA"]
    bf, f32 = jnp.bfloat16, jnp.float32

    def dense(*shape):
        return (shape, bf, ("normal", shape[-2] ** -0.5))

    def mlp(n):
        return {"w_gate": dense(n, d, ff), "w_up": dense(n, d, ff),
                "w_down": dense(n, ff, d)}

    ones = lambda *s: (s, f32, ("const", 1.0))
    return {
        "embed": ((m["V"], d), bf, ("normal", 0.02)),
        "ln_f": ones(d),
        MAMBA: {
            "ln": ones(nM, d), "ln_mlp": ones(nM, d), "mlp": mlp(nM),
            "mixer": {
                "in_proj": dense(nM, d, m["proj"]),
                "conv_w": ((nM, m["C"], m["K"]), f32, ("normal", 0.5)),
                "conv_b": ((nM, m["C"]), f32, ("normal", 0.1)),
                # Mamba-2's own init: A in [1, 16], dt in [1e-3, 1e-1]
                "a_log": ((nM, m["H"]), f32, ("log_uniform", 1.0, 16.0)),
                "dt_bias": ((nM, m["H"]), f32, ("inv_softplus", 1e-3, 1e-1)),
                "d_skip": ones(nM, m["H"]),
                "norm": ones(nM, m["di"]),
                "out_proj": dense(nM, m["di"], d),
            },
        },
        ATTN: {
            "ln": ones(nA, d), "ln_mlp": ones(nA, d), "mlp": mlp(nA),
            "mixer": {
                "wq": dense(nA, d, m["hq"] * m["hd"]),
                "wk": dense(nA, d, m["hkv"] * m["hd"]),
                "wv": dense(nA, d, m["hkv"] * m["hd"]),
                "wo": dense(nA, m["hq"] * m["hd"], d),
            },
        },
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[2], tuple)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, dtype, how):
    kind = how[0]
    if kind == "const":
        return jnp.full(shape, how[1], dtype)
    if kind == "normal":
        return (jax.random.normal(key, shape, jnp.float32) * how[1]).astype(dtype)
    lo, hi = math.log(how[1]), math.log(how[2])
    u = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    if kind == "log_uniform":
        return jnp.log(u).astype(dtype)
    return (u + jnp.log(-jnp.expm1(-u))).astype(dtype)  # softplus^-1(u)


def init_params(seed: int, cfg: dict) -> dict:
    """Seeded weights made on the default device, one leaf per call (so no
    fp32 copy of more than one leaf is ever held).  Any whole-number seed
    (also above 2**32) gives its own weights."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    key = jax.random.fold_in(jax.random.key(lo), hi)
    leaves, tree = jax.tree_util.tree_flatten(shapes(cfg), is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [_draw(k, s, dt, how) for k, (s, dt, how) in zip(keys, leaves)])


# ---------------------------------------------------------------------------
# the forward pass, one layer at a time
# ---------------------------------------------------------------------------

def _to_bf16(x):
    """Round fp32 to bf16 with ``reduce_precision``, which the compiler
    keeps (an ``astype`` round trip may be folded away)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _act(x, mode):
    """An activation where the program holds it in bf16 (the residual
    stream, a product's input): as it is, or under the ``FP8`` control
    rounded to fp8 e4m3 (3 stored mantissa bits)."""
    if mode == FP8:
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    return x


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mlp(p, h, mode):
    h = _act(h, mode)
    g = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
    return _act(g, mode) @ p["w_down"]


def _ssd(xh, dt, a, bm, cm, round_state):
    """Sequential recurrence. xh (B,T,H,P), dt (B,T,H), bm/cm (B,T,N);
    ``round_state`` rounds the state to bf16 after every update."""

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t * a)[:, :, None, None] * s
             + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        if round_state:
            s = _to_bf16(s)
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t)

    b, _, h, p = xh.shape
    s0 = jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32)
    tm = lambda v: jnp.swapaxes(v, 0, 1)
    _, ys = jax.lax.scan(step, s0, (tm(xh), tm(dt), tm(bm), tm(cm)))
    return tm(ys)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mamba_layer(lp, x, frozen, mode):
    cfg = dict(frozen)
    m, lp = dims(cfg), _f32(lp)
    p = lp["mixer"]
    b, t, _ = x.shape
    u = _act(_rms(x, lp["ln"], cfg["rms_norm_eps"]), mode) @ p["in_proj"]
    z, xbc, dt = jnp.split(u, [m["di"], m["di"] + m["C"]], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (m["K"] - 1, 0), (0, 0)))
    conv = sum(padded[:, m["K"] - 1 - j: m["K"] - 1 - j + t] * p["conv_w"][:, j]
               for j in range(m["K"]))
    xbc = _act(jax.nn.silu(conv + p["conv_b"]), mode)
    xs, bm, cm = jnp.split(xbc, [m["di"], m["di"] + m["N"]], axis=-1)
    xh = xs.reshape(b, t, m["H"], m["P"])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _ssd(xh, dt, -jnp.exp(p["a_log"]), bm, cm, mode == BF16_STATE)
    y = (y + p["d_skip"][:, None] * xh).reshape(b, t, m["di"])
    y = _rms(y * jax.nn.silu(z), p["norm"], cfg["rms_norm_eps"])
    x = _act(x + cfg["residual_multiplier"] * (_act(y, mode) @ p["out_proj"]),
             mode)
    return _act(x + cfg["residual_multiplier"] * _mlp(
        lp["mlp"], _rms(x, lp["ln_mlp"], cfg["rms_norm_eps"]), mode), mode)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attn_layer(lp, x, frozen, mode):
    cfg = dict(frozen)
    m, lp = dims(cfg), _f32(lp)
    p = lp["mixer"]
    b, t, _ = x.shape
    h = _act(_rms(x, lp["ln"], cfg["rms_norm_eps"]), mode)
    q = _act(h @ p["wq"], mode).reshape(b, t, m["hkv"], m["hq"] // m["hkv"], m["hd"])
    k = _act(h @ p["wk"], mode).reshape(b, t, m["hkv"], m["hd"])
    v = _act(h @ p["wv"], mode).reshape(b, t, m["hkv"], m["hd"])
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one(qkv):  # one sequence at a time: (Hkv, G, T, T) scores
        q1, k1, v1 = qkv
        s = jnp.einsum("qhgd,khd->hgqk", q1, k1) * cfg["attention_multiplier"]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", _act(w, mode), v1)

    out = jax.lax.map(one, (q, k, v)).reshape(b, t, m["hq"] * m["hd"])
    x = _act(x + cfg["residual_multiplier"] * (_act(out, mode) @ p["wo"]),
             mode)
    return _act(x + cfg["residual_multiplier"] * _mlp(
        lp["mlp"], _rms(x, lp["ln_mlp"], cfg["rms_norm_eps"]), mode), mode)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(embed, ln_f, xs, want, frozen, mode):
    """log p at ``want`` (R, 17) from the final states ``xs`` (R, d)."""
    cfg = dict(frozen)
    xn = _act(_rms(xs, ln_f.astype(jnp.float32), cfg["rms_norm_eps"]), mode)
    logits = xn @ embed.astype(jnp.float32).T / cfg["logits_scaling"]
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(lp, want, axis=-1)


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, list))))


def _mode(control) -> str | None:
    """``control``: False (the reference), True (the cell's control,
    ``FP8``) or a name in ``CONTROLS``."""
    if control is True:
        return FP8
    if control and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    return control or None


def hidden(params, tokens, cfg: dict, *, control=False):
    """The final states (B, T, d) fp32 of ``tokens`` (B, T), one layer's
    weights in fp32 at a time."""
    frozen, mode = _freeze(cfg), _mode(control)
    with jax.default_matmul_precision("highest"):
        x = _act(params["embed"][tokens].astype(jnp.float32)
                 * cfg["embedding_multiplier"], mode)
        index = {MAMBA: 0, ATTN: 0}
        for kind in cfg["layer_types"]:
            i = index[kind]
            index[kind] += 1
            lp = jax.tree_util.tree_map(lambda a: a[i], params[kind])
            x = (_mamba_layer(lp, x, frozen, mode) if kind == MAMBA
                 else _attn_layer(lp, x, frozen, mode))
    return x


def logits(params, tokens, cfg: dict) -> np.ndarray:
    """Full-sequence logits (B, T, V) fp32: a small model's whole output."""
    x = hidden(params, tokens, cfg)
    with jax.default_matmul_precision("highest"):
        xn = _rms(x, params["ln_f"].astype(jnp.float32), cfg["rms_norm_eps"])
        out = xn @ params["embed"].astype(jnp.float32).T / cfg["logits_scaling"]
    return np.asarray(out)


def scores(params, windows, cfg: dict, *, control=False,
           block: int = 256) -> np.ndarray:
    """Reference (or, with ``control``, a control's) probabilities for the
    generated positions of ``windows``: a list of requests ``{"prompt",
    "emitted", "ids"}``.  The sequence ``prompt + emitted[:-1]`` runs
    teacher-forced; at the position that produced ``emitted[i]`` the
    result holds the probabilities of the 16 ``ids`` and then of
    ``emitted[i]``, request after request: a flat array of
    ``17 * sum(len(emitted))`` float64.  Sequences are padded at the end to
    a multiple of ``block`` (causal: no real position sees the padding)."""
    frozen, mode = _freeze(cfg), _mode(control)
    seqs = [np.concatenate([w["prompt"], w["emitted"][:-1]]) for w in windows]
    t = -(-max(len(s) for s in seqs) // block) * block
    toks = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    x = hidden(params, toks, cfg, control=control)
    rows = -(-max(len(w["emitted"]) for w in windows) // block) * block
    out = []
    with jax.default_matmul_precision("highest"):
        for r, w in enumerate(windows):  # one head program for every request
            n_out, start = len(w["emitted"]), len(w["prompt"]) - 1
            want = np.zeros((rows, N_IDS + 1), np.int32)
            want[:n_out, :N_IDS] = w["ids"]
            want[:n_out, N_IDS] = w["emitted"]
            xs = jax.lax.dynamic_slice_in_dim(
                jnp.pad(x[r], ((0, rows), (0, 0))), start, rows)
            lp = _head(params["embed"], params["ln_f"], xs, want, frozen, mode)
            out.append(np.exp(np.asarray(lp, np.float64)[:n_out]).ravel())
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# the work: FLOPs and bytes at published widths
# ---------------------------------------------------------------------------

def _matmul_params(cfg: dict) -> int:
    """Weights a token's matrix products touch: every layer's projections
    and MLP, and the tied head (the embedding gather is no product)."""
    m = dims(cfg)
    mlp = 3 * m["d"] * m["ff"]
    mamba = m["d"] * m["proj"] + m["di"] * m["d"]
    attn = m["d"] * (m["hq"] + 2 * m["hkv"]) * m["hd"] + m["hq"] * m["hd"] * m["d"]
    return m["nM"] * (mamba + mlp) + m["nA"] * (attn + mlp) + m["V"] * m["d"]


def _token_flops(cfg: dict) -> int:
    """One token's FLOPs outside attention over its context: the matrix
    products (2 a multiply-add), the conv, and the SSD update and readout
    (state decay, outer-product add: 3 a state element; readout 2)."""
    m = dims(cfg)
    ssd = 5 * m["H"] * m["P"] * m["N"] + 2 * m["C"] * m["K"]
    return 2 * _matmul_params(cfg) + m["nM"] * ssd


def _attn_flops(cfg: dict, context) -> float:
    """Attention FLOPs of tokens that see ``context`` positions in all:
    scores and values, 2 x 2 a head-width multiply-add, per layer."""
    m = dims(cfg)
    return 4.0 * m["nA"] * m["hq"] * m["hd"] * context


def weight_bytes(cfg: dict) -> int:
    """Every weight as stored (bf16 matrices, fp32 vectors)."""
    leaves = jax.tree_util.tree_leaves(shapes(cfg), is_leaf=_is_spec)
    return sum(math.prod(s) * jnp.dtype(dt).itemsize for s, dt, _ in leaves)


def slot_state_bytes(cfg: dict) -> int:
    """One slot's recurrent state: fp32 SSD state and conv window of every
    Mamba layer."""
    m = dims(cfg)
    return 4 * m["nM"] * (m["H"] * m["P"] * m["N"] + (m["K"] - 1) * m["C"])


def kv_token_bytes(cfg: dict) -> int:
    """KV of one token over the attention layers, bf16."""
    m = dims(cfg)
    return 2 * m["nA"] * 2 * m["hkv"] * m["hd"]


def step_work(cfg: dict, steps: int, slot_steps: int,
              context: int) -> tuple[float, float]:
    """(FLOPs, bytes) a decode step's least time is held to, summed over
    ``steps`` steps that decoded ``slot_steps`` live slots over ``context``
    positions in all: the weights read once a step, each live slot's
    state read and written, its KV read at its length and one position
    written."""
    flops = slot_steps * _token_flops(cfg) + _attn_flops(cfg, context)
    nbytes = (steps * weight_bytes(cfg)
              + slot_steps * (2 * slot_state_bytes(cfg) + kv_token_bytes(cfg))
              + context * kv_token_bytes(cfg))
    return float(flops), float(nbytes)


def model_flops(cfg: dict, tokens: int, context: int, heads: int) -> float:
    """The model's own FLOPs for ``tokens`` tokens that see ``context``
    positions in all, ``heads`` of which compute logits (a decoded token
    does; a prefill, at its last token only)."""
    m = dims(cfg)
    head = 2 * m["V"] * m["d"]
    return float(tokens * (_token_flops(cfg) - head) + heads * head
                 + _attn_flops(cfg, context))
