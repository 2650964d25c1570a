"""Open-loop detector streams through the program's ``StreamServer``.

Traffic file keys: ``streams`` (N), ``sample_rate`` (Hz), ``chunk``
(samples per submit), ``phases`` ("spread": the stream start times are N
evenly spaced points of one window period, assigned to streams by a seeded
permutation; "aligned": every stream on one schedule), ``lead_s`` (traffic
before the measured window, counted as set-up), ``grace_s`` (how long past
the window an answer is waited for), ``server`` (``ServerConfig`` fields),
``strain`` (``gwdata.StrainSource`` parameters).

Chunk ``j`` of stream ``i`` is due when its last sample exists:
``phase_i + (j + 1) * chunk / sample_rate`` after the start.  One thread
submits every chunk at its due time, whatever the server does, and records
how late it ran.  A window's latency runs from the due time of its last
sample to the moment its score reaches the ``on_score`` callback.
"""

from __future__ import annotations

import gc
import time
from contextlib import nullcontext

import numpy as np

from perfbench import harness, stats, sut
from perfbench.gwdata import StrainSource


class _TimedEngine:
    """Proxy that records each ``push_many`` call: (start, end, real
    streams, samples per stream).  Everything else goes to the engine."""

    def __init__(self, engine, real_ids: set, annotate):
        self._engine = engine
        self._real = real_ids
        self._annotate = annotate
        self.calls: list[tuple[float, float, int, int]] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def push_many(self, stream_ids, chunks):
        with self._annotate("pb.push_many"):
            t0 = time.perf_counter()
            out = self._engine.push_many(stream_ids, chunks)
            t1 = time.perf_counter()
        n_real = sum(1 for s in stream_ids if s in self._real)
        self.calls.append((t0, t1, n_real, int(np.shape(chunks)[1])))
        return out


class _HostProbe:
    """What the host did while the generator ran late.  For each submit
    more than ``stall_s`` behind its due time: how late, the wall seconds
    since the generator's previous submit, and the process's CPU seconds
    (all threads) in them; a share near 0 says the process was not running,
    near 1 or above that another thread held it.  Also every garbage
    collection's pause."""

    def __init__(self, stall_s: float = 0.02):
        self.stall_s = stall_s
        self.stalls: list[tuple[float, float, float]] = []
        self.pauses: list[tuple[int, float]] = []
        self._gc_t0 = None
        self._prev = (time.perf_counter(), time.process_time())
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._gc_t0))

    def mark(self, late: float) -> None:
        now = (time.perf_counter(), time.process_time())
        if late > self.stall_s:
            self.stalls.append((late, now[0] - self._prev[0],
                                now[1] - self._prev[1]))
        self._prev = now

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def summary(self) -> dict:
        late, wall, cpu = max(self.stalls, default=(None, None, None))
        return {"line": "host_stalls", "over_20ms": len(self.stalls),
                "worst_late_ms": late and late * 1e3,
                "worst_cpu_share": cpu / wall if wall else None,
                "gc_collections": len(self.pauses),
                "gc_full": sum(1 for g, _ in self.pauses if g == 2),
                "gc_max_ms": max((d for _, d in self.pauses), default=0) * 1e3}


def schedule(traffic: dict, timesteps: int, total_s: float,
             rng: np.random.Generator):
    """(phases (N,), chunk due times (N, J)) relative to the start; J is
    the number of chunks per stream that fall due within ``total_s``."""
    n, rate, chunk = traffic["streams"], traffic["sample_rate"], \
        traffic["chunk"]
    if timesteps % chunk:
        raise ValueError(f"chunk {chunk} must divide the window {timesteps}")
    window_s = timesteps / rate
    if traffic["phases"] == "spread":
        phases = rng.permutation(np.arange(n) / n * window_s)
    elif traffic["phases"] == "aligned":
        phases = np.zeros(n)
    else:
        raise ValueError(f"unknown phases {traffic['phases']!r}")
    period = chunk / rate
    n_chunks = int(np.floor((total_s - phases.min()) / period))
    due = phases[:, None] + period * np.arange(1, n_chunks + 1)[None, :]
    return phases, due


def warm_up(engine, server_cfg: dict, n_streams: int, chunk: int,
            timesteps: int, input_dim: int) -> None:
    """Compile every program the run can call: through a server that is
    not started, tick by tick, ``k`` fresh streams advance one whole window
    together for every ``k`` a tick can gather, so every batch width the
    server pads to and every window-completion group size is built once."""
    server = sut.build_server(engine, server_cfg, on_score=lambda s, x: None)
    widest = min(n_streams, server.config.max_coalesce)
    zeros = np.zeros((chunk, input_dim), np.float32)
    for k in range(1, widest + 1):
        ids = [("warm", k, j) for j in range(k)]
        for _ in range(timesteps // chunk):
            for sid in ids:
                server.submit(sid, zeros)
            server.drain()
        for sid in ids:
            server.close_stream(sid)


def run(ctx) -> "harness.Outcome":
    import jax

    cell, seed = ctx.cell, ctx.seed
    cfg, tr = cell.config, cell.traffic
    t_win, chunk, rate = cfg["timesteps"], tr["chunk"], tr["sample_rate"]
    n = tr["streams"]
    lead, grace = tr["lead_s"], tr["grace_s"]
    total_s = lead + ctx.seconds
    annotate = jax.profiler.TraceAnnotation if ctx.trace else \
        (lambda name: nullcontext())

    # -- set-up: weights, traffic, engine, every program ----------------------
    params = cell.model.init_params(seed, cfg)
    jax.block_until_ready(params)
    rng = np.random.default_rng(seed)
    phases, due = schedule(tr, t_win, total_s, rng)
    n_chunks = due.shape[1]
    src = StrainSource(sample_rate=rate, **tr["strain"])
    strain = src.strain(rng, n, n_chunks * chunk)
    pieces = strain.reshape(n, n_chunks, chunk, cfg["input_dim"])

    engine = sut.build_engine(params, cfg)
    warm_up(engine, tr["server"], n, chunk, t_win, cfg["input_dim"])
    timed = _TimedEngine(engine, set(range(n)), annotate)
    got: list[list] = [[] for _ in range(n)]

    def on_score(sid, score):
        got[sid].append((time.perf_counter(), float(score[0])))

    server = sut.build_server(timed, tr["server"], on_score=on_score)

    # the chunks in due order; equal due times go out together
    flat_due = due.ravel()
    order = np.argsort(flat_due, kind="stable")
    sid_of = (order // n_chunks).tolist()
    j_of = (order % n_chunks).tolist()
    due_sorted = flat_due[order]
    starts = np.flatnonzero(np.r_[True, np.diff(due_sorted) > 0])
    bounds = np.r_[starts, len(order)].tolist()
    group_due = due_sorted[starts].tolist()

    if ctx.trace:
        jax.profiler.start_trace(str(ctx.trace_dir))
    server.start()
    late: list[float] = []
    probe = None
    window_ann = annotate("pb.window")
    fill0 = fill1 = None
    c0 = c1 = None
    t0 = time.perf_counter() + 0.01
    w0, w1 = t0 + lead, t0 + total_s
    in_window = False
    try:
        for g, d in enumerate(group_due):
            t_due = t0 + d
            now = time.perf_counter()
            if now < t_due:
                time.sleep(t_due - now)
            if not in_window and t_due >= w0:
                in_window = True
                probe = _HostProbe()
                fill0 = server.stats.batch_fill.copy()
                c0 = ctx.compiles.snapshot()
                window_ann.__enter__()
            with annotate("pb.submit"):
                for k in range(bounds[g], bounds[g + 1]):
                    sid = sid_of[k]
                    server.submit(sid, pieces[sid, j_of[k]])
            if in_window:
                late.append(time.perf_counter() - t_due)
                probe.mark(late[-1])
        if not in_window:  # a window too short for any chunk to fall due
            fill0 = server.stats.batch_fill.copy()
            c0 = ctx.compiles.snapshot()
            window_ann.__enter__()
        now = time.perf_counter()
        if now < w1:
            time.sleep(w1 - now)
        fill1 = server.stats.batch_fill.copy()
        window_ann.__exit__(None, None, None)

        # every window due in [w0, w1], waited for up to grace_s past w1
        win_due = phases[:, None] + (t_win / rate) * np.arange(
            1, n_chunks * chunk // t_win + 1)[None, :]
        last = [int(np.searchsorted(t0 + win_due[i], w1, side="right"))
                for i in range(n)]
        deadline = w1 + grace
        while time.perf_counter() < deadline and any(
                len(got[i]) < last[i] for i in range(n)):
            time.sleep(0.005)
        c1 = ctx.compiles.snapshot()
    finally:
        if probe is not None:
            probe.close()
        server.stop(drain=False)
        if ctx.trace:
            jax.profiler.stop_trace()
    summary = server.stats.summary()
    mem = harness.memory_peak(ctx.chips)

    # -- the answers ----------------------------------------------------------
    found, missing = answers(got, t0 + win_due, w0, w1)
    lat = [t_score - t_due for _, _, t_due, t_score, _ in found]
    lat_due = [t_due for _, _, t_due, _, _ in found]
    windows = [strain[i, k * t_win: (k + 1) * t_win]
               for i, k, _, _, _ in found]
    scores = [s for _, _, _, _, s in found]
    attempted = len(found) + missing
    values = {}
    if lat:
        values["latency_p50_ms"] = stats.percentile(lat, 50) * 1e3
        values["latency_p95_ms"] = stats.percentile(lat, 95) * 1e3
    ticks = fill1 - fill0
    info = [
        {"line": "generator", "groups_in_window": len(late),
         "late_p50_ms": stats.percentile(late, 50) * 1e3 if late else None,
         "late_p99_ms": stats.percentile(late, 99) * 1e3 if late else None,
         "late_max_ms": max(late) * 1e3 if late else None},
        probe.summary() if probe else {"line": "host_stalls"},
        {"line": "compiles_in_window",
         **{k: c1[k] - c0[k] for k in c0}},
        {"line": "windows", "due": attempted,
         "scored": attempted - missing, "missing": missing,
         "latency_samples": len(lat),
         "latency_max_ms": max(lat) * 1e3 if lat else None},
        {"line": "latency_trend", **_trend(lat, lat_due)},
        {"line": "server", **{k: v for k, v in summary.items()
                             if not k.startswith("latency")}},
    ]
    layer = None
    if ctx.trace:
        from perfbench.trace import Trace

        trace = Trace.from_dir(ctx.trace_dir)
        lo, hi = trace.window()
        calls = [c for c in timed.calls if w0 <= c[0] <= w1]
        layer = harness.LayerRun(
            cell=cell, trace=trace, lo=lo, hi=hi, chips=ctx.chips,
            device_kind=jax.devices()[0].device_kind,
            counts={
                "ticks": sum(ticks.values()),
                "real_streams_per_tick": sum(k * v for k, v in ticks.items()),
                "push_many_calls": len(calls),
                "push_many_s": sum(b - a for a, b, _, _ in calls),
                "step_row_steps": sum(r * t for _, _, r, t in calls),
                "step_rows": sum(r for _, _, r, _ in calls),
                "windows_scored": sum(
                    1 for i in range(n) for t, _ in got[i] if w0 <= t <= w1),
            })
    del server, timed, engine
    gc.collect()
    return harness.Outcome(
        params=params, attempted=attempted, missing=missing,
        windows=np.asarray(windows, np.float32).reshape(
            -1, t_win, cfg["input_dim"]),
        scores=np.asarray(scores, np.float64), values=values,
        setup_s=w0 - ctx.t_start, memory_peak_bytes=mem, info=info,
        layer=layer)


def answers(got, due, w0: float, w1: float):
    """Match each stream's scores to its windows, in order.

    ``got[i]``: stream ``i``'s ``(time, score)`` in arrival order (window
    ``k`` is the ``k``-th score of the stream); ``due[i, k]``: when window
    ``k``'s last sample was due, on the same clock.  Returns the windows due
    in ``[w0, w1]`` that were scored, as ``(stream, window, due, scored at,
    score)``, and how many such windows were never scored."""
    found, missing = [], 0
    for i, row in enumerate(due):
        for k in np.flatnonzero((row >= w0) & (row <= w1)).tolist():
            if k < len(got[i]):
                t_score, s = got[i][k]
                found.append((i, k, float(row[k]), t_score, s))
            else:
                missing += 1
    return found, missing


def _trend(lat, lat_due) -> dict:
    """p95 latency of the windows due in the first and in the last quarter
    of the window: a backlog that grows shows as a rising tail."""
    if len(lat) < 8:
        return {}
    lat, lat_due = np.asarray(lat), np.asarray(lat_due)
    q1, q3 = np.quantile(lat_due, [0.25, 0.75])
    return {"p95_first_quarter_ms": stats.percentile(lat[lat_due <= q1], 95) * 1e3,
            "p95_last_quarter_ms": stats.percentile(lat[lat_due >= q3], 95) * 1e3}
