"""Time a wavefront call in each layer-0 form, at the archive's shape.

    PYTHONPATH=src python -m benchmarks.layer0_forms [--batch 4096] \
        [--steps 100] [--width 128] [--layers 2] [--widths 1 2 4 8 16]

One fp32 segment of ``--layers`` layers packed to ``--width`` lanes runs
over ``--batch`` windows of ``--steps`` samples (batch tile 256).  For each
layer-0 input width D in ``--widths`` it times the ``narrow`` form (the
kernel projects the raw input on the VPU) against the ``stream`` form (the
same input padded to the pack width: an XLA matmul writes the gate tensor
to HBM and the kernel streams it back), then the ``repeat`` form of an
8-wide time-invariant input against that input broadcast over the steps
and streamed.  Each row is one JSON line: milliseconds per call (the best
of three passes of 40 back-to-back calls), whether the two forms gave
the same hidden sequence bit for bit, and the device that ran them.
``NARROW_MAX_IN`` is set from these rows.  Off a TPU the tool exits 1 and
prints no row: the kernels would run in the interpreter, whose times say
nothing about the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.lstm_stack.lstm_stack import lstm_stack
from repro.kernels.lstm_stack.ops import lstm_stack_op

LATENT = 8  # the gw_nominal decoder's input width


def ms_per_call(fn, arg, n: int = 40) -> float:
    jax.block_until_ready(fn(arg))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(arg)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / n * 1e3)
    return best


def row(name: str, fn_a, arg_a, fn_b, arg_b) -> dict:
    a, b = np.asarray(fn_a(arg_a)), np.asarray(fn_b(arg_b))
    dev = jax.devices()[0]
    return {"case": name, "platform": dev.platform,
            "device_kind": dev.device_kind, "ms": ms_per_call(fn_a, arg_a),
            "baseline_ms": ms_per_call(fn_b, arg_b),
            "bit_equal": bool(np.array_equal(a, b)),
            "max_abs_diff": float(np.abs(a - b).max())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--widths", type=int, nargs="+", default=[1, 2, 4, 8, 16])
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"layer0_forms: no TPU (JAX backend "
                 f"{jax.default_backend()!r}); the times would be the "
                 f"interpreter's")
    b, t, w, n_layers = args.batch, args.steps, args.width, args.layers
    block_b = min(256, b)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    st = {"w_x": jax.random.normal(ks[0], (n_layers, w, 4 * w)) * 0.1,
          "w_h": jax.random.normal(ks[1], (n_layers, w, 4 * w)) * 0.1,
          "b": jax.random.normal(ks[2], (n_layers, 4 * w)) * 0.1}
    h0 = c0 = jnp.zeros((n_layers, b, w))
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, w - x.shape[-1])))  # noqa: E731
    # the padded input is as wide as the pack: lstm_stack_op streams it
    stream = jax.jit(lambda x: lstm_stack_op(
        pad(x), st, h0, c0, block_b=block_b, alias_state=False)[0])
    # the kernel's narrow form at any width, past the op's limit too
    narrow = jax.jit(lambda x: jnp.swapaxes(lstm_stack(
        x, st["w_x"], st["w_h"], st["b"], h0, c0, form="narrow",
        block_b=block_b, alias_state=False)[0], 0, 1))
    for d in args.widths:
        x = jax.random.normal(jax.random.fold_in(ks[3], d), (b, t, d))
        print(json.dumps({"D": d, **row("narrow", narrow, x, stream, x)}),
              flush=True)
    repeat = jax.jit(lambda z: lstm_stack_op(
        z, st, h0, c0, timesteps=t, block_b=block_b, alias_state=False)[0])
    broadcast = jax.jit(lambda z: stream(
        jnp.broadcast_to(z[:, None], (b, t, z.shape[-1]))))
    latent = jax.random.normal(ks[3], (b, LATENT))
    print(json.dumps({"D": LATENT, **row("repeat", repeat, latent,
                                         broadcast, latent)}), flush=True)


if __name__ == "__main__":
    main()
