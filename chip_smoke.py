#!/usr/bin/env python3
"""Chip smoke: the GW stream-serving path on a TPU, checked against references.

One process drives the paper's use case through the entry points a user
calls, with random weights from ``--seed`` and synthetic strain from
``data/gw.py``:

* ``gw_nominal`` (LSTM autoencoder 32, 8, 8, 32; window T=100) with fp32,
  then bf16, then int8 weight storage, and ``gw_small`` (9, 9) at fp32:
  - calibrate on background windows (batch path: the wavefront kernel);
  - serve a few streams x a few windows through ``StreamServer`` at chunk
    lengths 1 and 25 (the step kernel's straight-line and loop bodies) and
    100 (above the plan's chunk_len: the wavefront kernel).
* Every score is compared with a reference computed on the same chip:
  fp32 with the ``naive`` backend at full matmul precision, bf16/int8 with
  ``kernels/lstm_stack/ref.py`` on the same packed weights.
* A phase fails if the engine fell back from ``fused_step``, if a compiled
  program holds no Pallas kernel (``tpu_custom_call``), if the server
  swallowed an error, dropped a chunk or lost a window, if a score is out
  of tolerance, or if streamed scores differ from the engine's batch
  scores of the same windows.

``--chips 4`` runs only the multi-chip path instead: ``gw_nominal`` batch
scoring with ``placement="sharded"``, bit-equal to the local fused stack
on the same windows, with the devices each stage's weights sit on.

Run from the checkout root on a TPU host::

    python chip_smoke.py [--chips 4]

Earlier stdout lines are one JSON row per phase.  The last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed;
without a TPU, or on any failure, the script exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.gw import GW_MODELS  # noqa: E402
from repro.core.autoencoder import (  # noqa: E402
    init_autoencoder,
    reconstruction_error,
)
from repro.core.quant import kernel_safe  # noqa: E402
from repro.data.gw import GwDataConfig, GwDataset  # noqa: E402
from repro.kernels.lstm_stack.ops import (  # noqa: E402
    apply_gate_scales,
    normalize_scales,
)
from repro.kernels.lstm_stack.ref import lstm_stack_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.engine import StreamingAnomalyEngine  # noqa: E402
from repro.serve.server import ServerConfig, StreamServer  # noqa: E402

#: served fp32 scores vs the ``naive`` fp32 reference at HIGHEST precision
#: (max relative error over windows).  A score is the mean of 100 squared
#: residuals of O(1) whitened strain, so fp32 rounding in the recurrence,
#: in the transcendentals and in the mean moves it by ~1e-7 (1.1e-7 on a
#: v5e, 1.6e-7 on the CPU).  bf16 weight storage moves gw_nominal scores by
#: 2e-5 (on a v5e and on the CPU), so this bound sits between the two; the
#: ``fp32_rejects_bf16`` row checks on the chip that bf16 scores fall
#: outside it.
FP32_RTOL = 1e-6
#: bf16/int8 served scores vs ``ref.py`` on the same packed codes: both
#: sides compute in fp32 on identical weights (the codes are exact at
#: fp32), so only rounding order and transcendental implementations differ
#: — the same budget as fp32.
PACKED_RTOL = 1e-6

STREAMS = 3          # concurrent streams per served phase
WINDOWS = 2          # windows per stream per served phase
CALIBRATION = 64     # background windows for the batch path
CHUNKS = (1, 25, 100)
PHASES = (
    ("gw_nominal", "fp32"),
    ("gw_nominal", "bf16"),
    ("gw_nominal", "int8"),
    ("gw_small", "fp32"),
)


class CompileLog:
    """Compile seconds and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want) / np.abs(want)))


def has_kernel(jitted, *args) -> bool:
    """The compiled program holds a Pallas TPU kernel: neither interpret
    mode nor a reference stood in for it."""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------

def naive_scores(params, cfg, windows) -> np.ndarray:
    """fp32 reference: the layer-by-layer ``naive`` backend, every matmul
    at full precision (the TPU default for fp32 is one bf16 pass)."""
    ref_cfg = dataclasses.replace(cfg, impl="naive", weight_dtype=None)
    score = jax.jit(lambda p, x: reconstruction_error(p, x, ref_cfg))
    with jax.default_matmul_precision("highest"):
        return np.asarray(score(params, jnp.asarray(windows)))


def _packed_stack_ref(packed, xs):
    """``ref.py`` over one packed segment, with the fused op's layer-0
    projection: (B, T, in) -> last layer's (B, T, hidden[-1])."""
    st = packed.stacked
    n_layers = packed.n_layers
    xs_p = packed.pad_input(xs)
    w0 = st["w_x"][0].astype(packed.dtype)
    xw0 = (xs_p @ w0).astype(jnp.float32)
    scales = st.get("scales")
    if scales is not None:
        xw0 = apply_gate_scales(xw0, normalize_scales(scales, n_layers)[0, 0])
    xw0 = xw0 + st["b"][0]
    h0, c0 = packed.zero_state(xs.shape[0])
    acts = kernel_safe(packed.acts)
    hs, _, _ = lstm_stack_ref(
        jnp.swapaxes(xw0, 0, 1), st["w_x"], st["w_h"], st["b"], h0, c0,
        scales=scales, sigma=acts.sigma, tanh=acts.tanh,
    )
    return jnp.swapaxes(hs, 0, 1)[..., : packed.hidden[-1]]


def packed_scores(engine, windows) -> np.ndarray:
    """bf16/int8 reference: ``ref.py`` on the engine's own packed weights,
    composed into the autoencoder score (RepeatVector, dense head, MSE)."""
    params = engine.params
    enc, dec = engine._exec_enc.packed, engine._exec_dec.packed

    def score(x):
        latent = _packed_stack_ref(enc, x)[:, -1, :]
        t = x.shape[1]
        h_seq = jnp.broadcast_to(
            latent[:, None, :], (x.shape[0], t, latent.shape[1])
        )
        h_seq = _packed_stack_ref(dec, h_seq)
        rec = h_seq @ params["dense"]["w"] + params["dense"]["b"]
        return jnp.mean((rec - x) ** 2, axis=(1, 2))

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(score)(jnp.asarray(windows)))


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def serve_windows(engine, chunk_len, streams):
    """Submit every stream's windows chunk by chunk, round-robin, through a
    running ``StreamServer``; returns (scores per stream, server stats)."""
    server = StreamServer(engine, ServerConfig())
    with server:
        for pos in range(0, streams.shape[1], chunk_len):
            for sid, series in enumerate(streams):
                server.submit(sid, series[pos : pos + chunk_len])
    scores = server.pop_scores()
    errors = server.pop_errors()
    per_stream = [
        np.concatenate(scores.get(sid, [np.zeros(0)]))
        for sid in range(len(streams))
    ]
    return per_stream, server.stats, errors


def run_model(model, weight_dtype, params, ds, log, rows, failures):
    cfg = dataclasses.replace(GW_MODELS[model], weight_dtype=weight_dtype)
    engine = StreamingAnomalyEngine(
        params, cfg, batch=1, impl="fused_step", tune="default"
    )
    plan = {
        "encoder": engine._exec_enc.plan.describe(),
        "decoder": engine._exec_dec.plan.describe(),
    }
    fallback = []
    if engine.effective_impl != "fused_step":
        fallback.append(f"effective impl {engine.effective_impl!r}")
    if engine.fallback_reason is not None:
        fallback.append(f"fallback: {engine.fallback_reason}")

    def reference(windows):
        if weight_dtype == "fp32":
            return naive_scores(params, cfg, windows), FP32_RTOL
        return packed_scores(engine, windows), PACKED_RTOL

    # -- batch path: calibration on background windows --------------------
    background = ds.background(CALIBRATION)
    c0 = log.seconds
    engine.calibrate(background, fpr=0.05)
    compile_s = log.seconds - c0
    got = engine.score(background)
    want, tol = reference(background)
    err = max_rel_err(got, want)
    # how far this storage moves scores from the fp32 model
    vs_fp32 = max_rel_err(got, naive_scores(params, cfg, background))
    kernel = has_kernel(
        engine._score_batch, params, engine._exec_enc, engine._exec_dec,
        jnp.asarray(background),
    )
    problems = list(fallback)
    if not kernel:
        problems.append("no tpu_custom_call in the batch forward")
    if not err <= tol:
        problems.append(f"max_rel_err {err:.3g} > {tol:g}")
    report(rows, failures, problems, {
        "phase": "calibrate", "model": model, "weight_dtype": weight_dtype,
        "chunk_len": None, "impl": engine.effective_impl, "plan": plan,
        "max_rel_err": err, "tol": tol, "max_rel_err_vs_fp32_ref": vs_fp32,
        "compile_s": round(compile_s, 3), "windows": len(got),
        "tpu_custom_call": kernel,
    })

    # -- served path: StreamServer at each chunk length --------------------
    windows = np.concatenate([ds.background(STREAMS * WINDOWS - 1),
                              ds.events(1)])
    streams = windows.reshape(STREAMS, WINDOWS * cfg.timesteps, cfg.input_dim)
    want, tol = reference(windows)
    want = want.reshape(STREAMS, WINDOWS)
    # the engine's one-shot batch scores of the same windows: streamed
    # scores equal them bit for bit, at every chunk length (on the CPU and
    # on a v5e)
    batch = np.asarray(engine.score(windows)).reshape(STREAMS, WINDOWS)
    for chunk_len in CHUNKS:
        c0 = log.seconds
        per_stream, stats, errors = serve_windows(engine, chunk_len, streams)
        compile_s = log.seconds - c0
        got = np.stack(per_stream) if all(
            len(s) == WINDOWS for s in per_stream) else None
        err = float("inf") if got is None else max_rel_err(got, want)
        bit_equal = got is not None and bool(np.array_equal(got, batch))
        kernel = has_kernel(
            engine._enc_step,
            jnp.zeros((1, chunk_len, cfg.input_dim), jnp.float32),
            engine._exec_enc.zero_state(1),
        )
        problems = list(fallback)
        if got is None:
            problems.append(
                f"windows scored per stream {[len(s) for s in per_stream]}, "
                f"want {WINDOWS}"
            )
        if not kernel:
            problems.append("no tpu_custom_call in the encoder step")
        if not err <= tol:
            problems.append(f"max_rel_err {err:.3g} > {tol:g}")
        if not bit_equal:
            problems.append("streamed scores differ from the batch scores")
        swallowed = {k: getattr(stats, k) for k in (
            "engine_errors", "callback_errors", "scheduler_restarts", "drops")}
        if any(swallowed.values()) or errors:
            problems.append(f"server swallowed errors: {swallowed} {errors}")
        report(rows, failures, problems, {
            "phase": "serve", "model": model, "weight_dtype": weight_dtype,
            "chunk_len": chunk_len, "impl": engine.effective_impl,
            "plan": plan, "max_rel_err": err, "tol": tol,
            "compile_s": round(compile_s, 3),
            "windows": stats.windows_scored, "tpu_custom_call": kernel,
            "bit_equal_to_batch": bit_equal,
            **swallowed,
        })
    return vs_fp32


def report(rows, failures, problems, row):
    row["ok"] = not problems
    if problems:
        row["problems"] = problems
        failures.append(f"{row['phase']} {row['model']} "
                        f"{row['weight_dtype']} {row['chunk_len']}: "
                        f"{problems}")
    rows.append(row)
    print(json.dumps(row), flush=True)


def one_chip(args, log, failures):
    ds = GwDataset(GwDataConfig(timesteps=100, seed=args.seed))
    rows = []
    bf16_vs_fp32 = 0.0
    for model, weight_dtype in PHASES:
        params = init_autoencoder(jax.random.PRNGKey(args.seed),
                                  GW_MODELS[model])
        try:
            vs_fp32 = run_model(model, weight_dtype, params, ds, log, rows,
                                failures)
        except Exception:  # noqa: BLE001 — report, go on to the next phase
            traceback.print_exc()
            failures.append(f"{model} {weight_dtype}: raised")
            continue
        if (model, weight_dtype) == ("gw_nominal", "bf16"):
            bf16_vs_fp32 = vs_fp32
    # the fp32 tolerance must tell bf16 from fp32 on this chip: bf16-stored
    # calibration scores against the fp32 reference fall outside it
    report(rows, failures,
           [] if bf16_vs_fp32 > FP32_RTOL else ["bf16 within fp32 tolerance"],
           {"phase": "fp32_rejects_bf16", "model": "gw_nominal",
            "weight_dtype": "bf16", "chunk_len": None,
            "max_rel_err_vs_fp32_ref": bf16_vs_fp32, "fp32_tol": FP32_RTOL})


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def stage_devices(executor):
    """Which device holds each stage's sub-stack: stage i of the
    ``stage`` mesh runs layers [i*L/S, (i+1)*L/S)."""
    mesh = executor.plan.mesh
    n_stages = mesh.shape["stage"]
    per = executor.plan.n_layers // n_stages
    return [
        {"stage": i, "layers": list(range(i * per, (i + 1) * per)),
         "device": f"{d.platform}:{d.id}"}
        for i, d in enumerate(mesh.devices.flat)
    ]


def four_chips(args, log, failures):
    if len(jax.devices()) < 4:
        failures.append(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
        return
    model = "gw_nominal"
    cfg = GW_MODELS[model]
    params = init_autoencoder(jax.random.PRNGKey(args.seed), cfg)
    ds = GwDataset(GwDataConfig(timesteps=100, seed=args.seed))
    windows = ds.background(CALIBRATION)
    c0 = log.seconds
    sharded = StreamingAnomalyEngine(params, cfg, placement="sharded")
    local = StreamingAnomalyEngine(params, cfg, impl="fused_stack")
    got = sharded.score(windows)
    want = local.score(windows)
    compile_s = log.seconds - c0
    placement = {
        "encoder": stage_devices(sharded._exec_enc),
        "decoder": stage_devices(sharded._exec_dec),
    }
    used = sorted({s["device"] for seg in placement.values() for s in seg})
    bit_equal = bool(np.array_equal(got, want))
    err = max_rel_err(got, want)
    err_ref = max_rel_err(got, naive_scores(params, cfg, windows))
    kernel = has_kernel(
        sharded._score_batch, params, sharded._exec_enc, sharded._exec_dec,
        jnp.asarray(windows),
    )
    problems = []
    if sharded.effective_impl != "fused_step" or sharded.fallback_reason:
        problems.append(f"sharded engine resolved {sharded.effective_impl!r}")
    if not kernel:
        problems.append("no tpu_custom_call in the sharded forward")
    if not bit_equal:  # the sharded backend's contract, on a v5e too
        problems.append(f"sharded != local: max_rel_err {err:.3g}")
    if not err_ref <= FP32_RTOL:
        problems.append(f"sharded vs naive max_rel_err {err_ref:.3g}")
    report([], failures, problems, {
        "phase": "sharded_vs_local", "model": model, "weight_dtype": "fp32",
        "chunk_len": None,
        "plan": {"encoder": sharded._exec_enc.plan.describe(),
                 "decoder": sharded._exec_dec.plan.describe(),
                 "local": local._exec_enc.plan.describe()},
        "stage_devices": placement, "devices_used": used,
        "devices_present": len(jax.devices()),
        "bit_equal": bit_equal, "max_rel_err": err,
        "max_rel_err_vs_naive": err_ref, "naive_tol": FP32_RTOL,
        "compile_s": round(compile_s, 3), "windows": len(got),
        "tpu_custom_call": kernel,
    })


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded batch path on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and synthetic strain")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform!r}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    log = CompileLog()
    failures: list[str] = []
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, log, failures)
    else:
        one_chip(args, log, failures)
    print(json.dumps({
        "phase": "summary", "wall_s": round(time.perf_counter() - t0, 3),
        "compile_s": round(log.seconds, 3), "compile_cache": cache_dir,
        "cache_hits": log.hits, "cache_misses": log.misses,
        "failures": len(failures),
    }), flush=True)
    if failures:
        for f in failures:
            print(f"FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
