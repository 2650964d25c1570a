"""Plain reference of the paper's LSTM autoencoder, its weights and its work.

Everything the benchmark needs to know about the architecture lives here,
independent of the program under test (nothing of ``src/`` is imported):

* ``init_params`` makes seeded fp32 weights on the device in one jitted
  call, laid out the way the serving engine takes them;
* ``scores`` is the plain forward pass: a ``lax.scan`` LSTM per layer
  (gate order i, f, g, o; ``c = f*c + i*g``; ``h = o*tanh(c)``), the
  encoder's last hidden as the latent, the latent repeated over the window
  (RepeatVector), the decoder, a dense head, and the mean squared
  reconstruction error per window.  Every dot is at HIGHEST precision;
* ``control_dot`` computes a dot in one bf16 pass (both operands rounded
  to bf16, exact products, fp32 accumulation): the arithmetic of
  ``Precision.DEFAULT`` for fp32 operands on a TPU, written out so that it
  is the same on any backend.  ``scores(..., control=True)`` is the
  correctness control;
* ``model_flops`` / ``kernel_work`` count the model's own work at the
  published widths, whatever the kernels pad to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def layer_dims(cfg: dict) -> list[tuple[int, int]]:
    """(in_dim, hidden) per LSTM layer; the first decoder layer consumes
    the latent (the last encoder layer's hidden)."""
    dims, lx = [], cfg["input_dim"]
    for i, h in enumerate(cfg["hidden"]):
        if i == cfg["latent_boundary"]:
            lx = cfg["hidden"][i - 1]
        dims.append((lx, h))
        lx = h
    return dims


def _init(key, cfg):
    dims = layer_dims(cfg)
    keys = jax.random.split(key, 2 * len(dims) + 1)
    params = {}
    for i, (d_in, h) in enumerate(dims):
        lim_x = (6.0 / (d_in + 4 * h)) ** 0.5
        lim_h = (6.0 / (h + 4 * h)) ** 0.5
        b = jnp.zeros((4 * h,), jnp.float32).at[h: 2 * h].set(1.0)
        params[f"lstm_{i}"] = {
            "w_x": jax.random.uniform(keys[2 * i], (d_in, 4 * h),
                                      jnp.float32, -lim_x, lim_x),
            "w_h": jax.random.uniform(keys[2 * i + 1], (h, 4 * h),
                                      jnp.float32, -lim_h, lim_h),
            "b": b,  # forget-gate bias 1
        }
    h_last, d_out = cfg["hidden"][-1], cfg["input_dim"]
    lim = (6.0 / (h_last + d_out)) ** 0.5
    params["dense"] = {
        "w": jax.random.uniform(keys[-1], (h_last, d_out), jnp.float32,
                                -lim, lim),
        "b": jnp.zeros((d_out,), jnp.float32),
    }
    return params


def init_params(seed: int, cfg: dict) -> dict:
    """Seeded fp32 weights, made on the default device in one jitted call.
    Any whole-number seed (also above 2**32) gives its own weights."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    key = jax.random.fold_in(jax.random.key(lo), hi)
    frozen = _freeze(cfg)
    return _init_jit(key, frozen)


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items()
        if k in ("input_dim", "hidden", "latent_boundary", "timesteps")
    ))


@functools.partial(jax.jit, static_argnums=1)
def _init_jit(key, frozen):
    return _init(key, dict(frozen))


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def highest_dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _to_bf16(x):
    """Round fp32 to bf16 with ``reduce_precision``, which the compiler
    keeps: a round trip through ``astype`` may be folded away as excess
    precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def control_dot(a, b):
    """One bf16 pass with fp32 accumulation (``Precision.DEFAULT`` on fp32
    operands): the products of two bf16 values are exact in fp32, so a
    HIGHEST dot of the rounded operands adds them as the MXU does."""
    return highest_dot(_to_bf16(a), _to_bf16(b))


def _lstm(p, xs, dot):
    """xs: (T, B, in) time-major -> (T, B, H) hidden sequence."""
    hidden = p["w_h"].shape[0]
    batch = xs.shape[1]

    def cell(carry, x_t):
        h, c = carry
        g = dot(x_t, p["w_x"]) + dot(h, p["w_h"]) + p["b"]
        i = jax.nn.sigmoid(g[:, :hidden])
        f = jax.nn.sigmoid(g[:, hidden: 2 * hidden])
        u = jnp.tanh(g[:, 2 * hidden: 3 * hidden])
        o = jax.nn.sigmoid(g[:, 3 * hidden:])
        c = f * c + i * u
        h = o * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((batch, hidden), jnp.float32)
    _, hs = jax.lax.scan(cell, (zero, zero), xs)
    return hs


def _scores(params, windows, n_layers, boundary, dot):
    """windows: (B, T, in) -> (B,) mean squared reconstruction error."""
    xs = jnp.swapaxes(windows.astype(jnp.float32), 0, 1)  # (T, B, in)
    h = xs
    for i in range(boundary):
        h = _lstm(params[f"lstm_{i}"], h, dot)
    latent = h[-1]
    h = jnp.broadcast_to(latent[None], (xs.shape[0],) + latent.shape)
    for i in range(boundary, n_layers):
        h = _lstm(params[f"lstm_{i}"], h, dot)
    rec = dot(h, params["dense"]["w"]) + params["dense"]["b"]
    return jnp.mean((rec - xs) ** 2, axis=(0, 2))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _scores_jit(params, windows, n_layers, boundary, control):
    return _scores(params, windows, n_layers, boundary,
                   control_dot if control else highest_dot)


def scores(params, windows, cfg: dict, *, control: bool = False,
           block: int = 4096) -> np.ndarray:
    """Reference (or, with ``control``, the one-pass control) scores of
    ``windows`` (B, T, in), computed in blocks of ``block`` rows so that it
    fits beside whatever else the process holds.  Every block is padded to
    the same row count, so one program serves every call."""
    windows = np.asarray(windows, np.float32)
    n = windows.shape[0]
    blk = min(block, n)
    out = np.empty(n, np.float64)
    for s in range(0, n, blk):
        part = windows[s: s + blk]
        if part.shape[0] < blk:
            part = np.concatenate(
                [part, np.zeros((blk - part.shape[0],) + part.shape[1:],
                                np.float32)])
        got = _scores_jit(params, part, len(cfg["hidden"]),
                          cfg["latent_boundary"], control)
        out[s: s + blk] = np.asarray(got, np.float64)[: min(blk, n - s)]
    return out


# ---------------------------------------------------------------------------
# the model's own work, at published widths
# ---------------------------------------------------------------------------

#: elementwise operations per hidden unit and time step after the gate
#: dots: 4 bias adds, 3 sigmoids, 2 tanh, c = f*c + i*g (3), h = o*tanh(c)
#: (1 beyond the tanh)
CELL_OPS = 4 + 3 + 2 + 3 + 1


def cell_flops(d_in: int, hidden: int, *, with_input: bool = True) -> int:
    """FLOPs of one LSTM cell step for one row."""
    dots = 2 * hidden * 4 * hidden + (2 * d_in * 4 * hidden if with_input else 0)
    return dots + CELL_OPS * hidden


def model_flops(cfg: dict) -> int:
    """FLOPs to score one window: every layer at every step, the dense head
    and the squared error mean."""
    t = cfg["timesteps"]
    per_step = sum(cell_flops(d, h) for d, h in layer_dims(cfg))
    head = 2 * cfg["hidden"][-1] * cfg["input_dim"] + cfg["input_dim"]
    mse = 3 * cfg["input_dim"]
    return t * (per_step + head + mse)


def segment_layers(cfg: dict, segment: str) -> list[tuple[int, int]]:
    dims = layer_dims(cfg)
    b = cfg["latent_boundary"]
    return dims[:b] if segment == "encoder" else dims[b:]


def kernel_work(cfg: dict, segment: str, row_steps: int, row_calls: int,
                calls: int) -> tuple[int, int]:
    """(FLOPs, bytes) that a fused LSTM-stack kernel has to do for one
    segment ("encoder" or "decoder") at published widths and fp32, over
    ``calls`` kernel calls that advance rows by ``row_steps`` row-steps in
    all (rows times steps, summed over calls) and take ``row_calls`` rows
    in all (rows summed over calls).

    FLOPs: every cell of the segment, except layer 0's input projection,
    which the serving path computes outside the kernel.  Bytes, the least
    any such kernel moves through HBM: per row-step, layer 0's projected
    gates in (4 H0) and the last layer's hidden out; per row and call,
    every layer's (h, c) in and out; per call, the weights and biases once.
    """
    layers = segment_layers(cfg, segment)
    flops_step = sum(
        cell_flops(d, h, with_input=i > 0) for i, (d, h) in enumerate(layers))
    h0, h_last = layers[0][1], layers[-1][1]
    stream = row_steps * (4 * h0 + h_last)
    state = row_calls * 2 * 2 * sum(h for _, h in layers)
    weights = calls * sum(
        (d * 4 * h if i > 0 else 0) + h * 4 * h + 4 * h
        for i, (d, h) in enumerate(layers))
    return row_steps * flops_step, 4 * (stream + state + weights)
