"""Closed-loop rescoring of archived strain through the engine's batch path.

Traffic file keys: ``windows_per_call`` (non-overlapping windows scored per
``StreamingAnomalyEngine.score`` call), ``pool`` (distinct batches of
strain made from the seed, used in turn), ``sample_rate`` and ``strain``
(``gwdata.StrainSource`` parameters).

One caller scores one batch after another, each call taking the host
array, moving it to the chip and reading the scores back, until the window
has run; windows/s is every window scored over all that time.
"""

from __future__ import annotations

import gc
import time
from contextlib import nullcontext

import numpy as np

from perfbench import harness, sut
from perfbench.gwdata import StrainSource


def run(ctx) -> "harness.Outcome":
    import jax

    cell, seed = ctx.cell, ctx.seed
    cfg, tr = cell.config, cell.traffic
    t_win, dim = cfg["timesteps"], cfg["input_dim"]
    per_call = tr["windows_per_call"]
    annotate = jax.profiler.TraceAnnotation if ctx.trace else \
        (lambda name: nullcontext())

    params = cell.model.init_params(seed, cfg)
    jax.block_until_ready(params)
    rng = np.random.default_rng(seed)
    src = StrainSource(sample_rate=tr["sample_rate"], **tr["strain"])
    pool = [src.strain(rng, 1, per_call * t_win).reshape(per_call, t_win, dim)
            for _ in range(tr["pool"])]
    engine = sut.build_engine(params, cfg)
    for batch in pool[:2]:
        engine.score(batch)  # compiles, then runs once warm

    if ctx.trace:
        jax.profiler.start_trace(str(ctx.trace_dir))
    results = []
    c0 = ctx.compiles.snapshot()
    try:
        with annotate("pb.window"):
            t0 = time.perf_counter()
            while True:
                with annotate("pb.score"):
                    results.append(engine.score(pool[len(results) % len(pool)]))
                t1 = time.perf_counter()
                if t1 - t0 >= ctx.seconds:
                    break
        c1 = ctx.compiles.snapshot()
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    mem = harness.memory_peak(ctx.chips)
    scored = len(results) * per_call
    values = {"windows_per_s": scored / (t1 - t0)}
    info = [
        {"line": "compiles_in_window", **{k: c1[k] - c0[k] for k in c0}},
        {"line": "windows", "calls": len(results), "scored": scored,
         "window_s": t1 - t0},
    ]
    layer = None
    if ctx.trace:
        from perfbench.trace import Trace

        trace = Trace.from_dir(ctx.trace_dir)
        lo, hi = trace.window()
        layer = harness.LayerRun(
            cell=cell, trace=trace, lo=lo, hi=hi, chips=ctx.chips,
            device_kind=jax.devices()[0].device_kind,
            counts={"windows_scored": scored, "score_calls": len(results)})
    del engine
    gc.collect()
    # every answer is compared: call i scored pool[i % len(pool)]
    index = np.concatenate([
        (i % len(pool)) * per_call + np.arange(per_call)
        for i in range(len(results))])
    return harness.Outcome(
        params=params, attempted=scored, missing=0,
        windows=np.concatenate(pool), index=index,
        scores=np.concatenate(results).astype(np.float64),
        values=values, setup_s=t0 - ctx.t_start, memory_peak_bytes=mem,
        info=info, layer=layer)
