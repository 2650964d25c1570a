"""The serving path's span recorder (``repro/telemetry.py``): durations,
self time, counters, snapshot/reset, per-thread span stacks, and the spans
in a profiler trace, nested on the device's clock."""

import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.core.autoencoder import AutoencoderConfig, init_autoencoder
from repro.serve.engine import StreamingAnomalyEngine
from repro.latency import SUB_BINS, LatencyHistogram
from repro.serve.server import ServerConfig, StreamServer


class FakeNs:
    """Stands in for ``perf_counter_ns``: advanced by hand, in us."""

    def __init__(self):
        self.ns = 0

    def __call__(self) -> int:
        return self.ns

    def advance_us(self, us: float) -> None:
        self.ns += int(us * 1e3)


@pytest.fixture
def clock(monkeypatch):
    fake = FakeNs()
    monkeypatch.setattr(telemetry, "_now", fake)
    return fake


def test_a_span_records_its_duration(clock):
    rec = telemetry.Recorder()
    with rec.span("engine.step") as sp:
        clock.advance_us(250)
    assert sp.seconds == pytest.approx(250e-6)
    st = rec.snapshot()["spans"]["engine.step"]
    assert st["count"] == 1
    assert st["total_s"] == pytest.approx(250e-6)
    # one sample: every quantile is the sample itself
    assert st["p50_us"] == st["p99_us"] == st["max_us"] == pytest.approx(250)
    assert st["self_p50_us"] == pytest.approx(250)


def test_self_time_leaves_out_the_children_on_the_same_thread(clock):
    rec = telemetry.Recorder()
    with rec.span("serve.tick"):
        clock.advance_us(100)
        with rec.span("engine.push_many"):
            clock.advance_us(50)
            with rec.span("engine.finish"):
                clock.advance_us(300)
            clock.advance_us(20)
        with rec.span("serve.deliver"):
            clock.advance_us(30)
        clock.advance_us(500)
    spans = rec.snapshot()["spans"]
    assert spans["serve.tick"]["p50_us"] == pytest.approx(1000)
    assert spans["serve.tick"]["self_p50_us"] == pytest.approx(600)
    assert spans["engine.push_many"]["p50_us"] == pytest.approx(370)
    assert spans["engine.push_many"]["self_p50_us"] == pytest.approx(70)
    assert spans["engine.finish"]["self_p50_us"] == pytest.approx(300)
    assert spans["serve.deliver"]["self_p50_us"] == pytest.approx(30)


def test_a_span_that_raises_still_records_and_unwinds(clock):
    rec = telemetry.Recorder()
    with pytest.raises(RuntimeError):
        with rec.span("outer"):
            with rec.span("inner"):
                clock.advance_us(40)
                raise RuntimeError("boom")
    with rec.span("after"):
        clock.advance_us(10)
    spans = rec.snapshot()["spans"]
    assert spans["inner"]["count"] == spans["outer"]["count"] == 1
    assert spans["outer"]["self_p50_us"] == 0.0
    # the stack unwound: "after" is nobody's child and nobody its parent
    assert spans["after"]["self_p50_us"] == pytest.approx(10)


def test_records_and_counters():
    rec = telemetry.Recorder()
    rec.record("serve.queue_wait", 2.5e-3)
    rec.record("serve.queue_wait", 0.5e-3)
    rec.count("engine.states_created")
    rec.count("engine.states_created", 3)
    snap = rec.snapshot()
    q = snap["spans"]["serve.queue_wait"]
    assert q["count"] == 2
    assert q["total_s"] == pytest.approx(3e-3)
    assert q["max_us"] == pytest.approx(2500)
    # within one bin
    assert 500 <= q["p50_us"] <= 500 * 2 ** (1 / SUB_BINS)
    assert snap["counters"] == {"engine.states_created": 4}


def test_percentiles_agree_with_the_histogram_past_the_binning_buffer():
    rec = telemetry.Recorder()
    rng = np.random.default_rng(3)
    us = rng.lognormal(4, 1, 3 * telemetry._BIN_EVERY + 17)
    for x in us:
        rec.record("s", x * 1e-6)
    want = LatencyHistogram()
    for x in us:
        want.record(x)
    st = rec.snapshot()["spans"]["s"]
    assert st["count"] == len(us)
    assert st["p50_us"] == want.percentile(50)
    assert st["p99_us"] == want.percentile(99)
    assert st["max_us"] == pytest.approx(us.max())


def test_snapshot_is_plain_and_reset_clears_it():
    rec = telemetry.Recorder()
    with rec.span("a"):
        pass
    rec.count("c")
    snap = rec.snapshot()
    assert set(snap) == {"spans", "counters"}
    assert set(snap["spans"]["a"]) == {"count", "total_s", "p50_us",
                                       "p99_us", "max_us", "self_p50_us"}
    rec.reset()
    assert rec.snapshot() == {"spans": {}, "counters": {}}
    with rec.span("a"):
        pass
    assert rec.snapshot()["spans"]["a"]["count"] == 1


def test_each_thread_keeps_its_own_span_stack():
    """A span open on one thread is no parent of a span on another: its
    self time is all of its duration."""
    rec = telemetry.Recorder()
    opened, other_done = threading.Event(), threading.Event()

    def outer():
        with rec.span("outer"):
            opened.set()
            assert other_done.wait(10)

    def other():
        assert opened.wait(10)
        with rec.span("other"):
            threading.Event().wait(0.02)
        rec.count("other.ran")
        other_done.set()

    threads = [threading.Thread(target=outer), threading.Thread(target=other)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    spans = rec.snapshot()["spans"]
    assert spans["other"]["p50_us"] >= 20000
    assert spans["outer"]["p50_us"] >= spans["other"]["p50_us"]
    assert spans["outer"]["self_p50_us"] == spans["outer"]["p50_us"]
    # the records of threads that have exited survive a new thread's arrival
    t = threading.Thread(target=lambda: rec.count("other.ran"))
    t.start()
    t.join(10)
    with rec.span("main"):
        pass
    snap = rec.snapshot()
    assert snap["counters"] == {"other.ran": 2}
    assert {"outer", "other", "main"} <= set(snap["spans"])


def test_the_module_functions_share_one_recorder():
    telemetry.reset()
    with telemetry.span("x"):
        telemetry.count("y")
    snap = telemetry.snapshot()
    assert snap["spans"]["x"]["count"] == 1
    assert snap["counters"]["y"] == 1
    telemetry.reset()


# -- the spans in a profiler trace ---------------------------------------------

_CFG = AutoencoderConfig(hidden=(9, 9), latent_boundary=1, timesteps=12)


def _host_spans(log_dir: Path) -> list[tuple[str, int, int]]:
    """(name, start_ns, end_ns) of the serving spans on the host planes."""
    from jax.profiler import ProfileData

    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    assert found, "the profiler wrote no .xplane.pb"
    data = ProfileData.from_file(str(found[-1]))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "engine.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def _inside(child, parents) -> bool:
    _, a, b = child
    return any(pa <= a and b <= pb for _, pa, pb in parents)


def test_the_spans_nest_in_a_profiler_trace(tmp_path):
    params = init_autoencoder(jax.random.PRNGKey(0), _CFG)
    engine = StreamingAnomalyEngine(params, _CFG, batch=1)
    server = StreamServer(engine, ServerConfig(max_coalesce=2))
    rng = np.random.default_rng(0)
    strain = rng.standard_normal((2, 2 * _CFG.timesteps, 1)).astype(
        np.float32)
    windows = rng.standard_normal((4, _CFG.timesteps, 1)).astype(np.float32)
    # compile outside the trace: the same shapes as below
    for pos in range(0, _CFG.timesteps, 6):
        for i in range(2):
            server.submit(("warm", i), strain[i, pos:pos + 6])
        server.drain()
    for i in range(2):
        server.close_stream(("warm", i))
    server.pop_scores()
    engine.score(windows)

    jax.profiler.start_trace(str(tmp_path))
    try:
        for pos in range(0, strain.shape[1], 6):
            for i in range(2):
                server.submit(i, strain[i, pos:pos + 6])
            server.tick()
        engine.score(windows)
    finally:
        jax.profiler.stop_trace()
    assert len(server.pop_scores()[0]) == 2

    spans = _host_spans(tmp_path)
    by = {}
    for ev in spans:
        by.setdefault(ev[0], []).append(ev)
    assert {"serve.submit", "serve.schedule", "serve.tick", "serve.deliver",
            "engine.push_many", "engine.step",
            "engine.new_state", "engine.finish", "engine.finish_sync",
            "engine.score", "engine.score_put",
            "engine.score_sync"} <= set(by)
    assert len(by["engine.finish_sync"]) == 2  # one per window completed
    for child, parent in [("engine.finish_sync", "engine.finish"),
                          ("engine.finish", "engine.push_many"),
                          ("engine.step", "engine.push_many"),
                          ("engine.new_state", "engine.push_many"),
                          ("engine.push_many", "serve.tick"),
                          ("serve.deliver", "serve.tick"),
                          ("engine.score_put", "engine.score"),
                          ("engine.score_sync", "engine.score")]:
        for ev in by[child]:
            assert _inside(ev, by[parent]), (child, parent)


def test_a_submit_that_waits_for_the_queue_records_its_wait():
    """``serve.submit_lock``: a submit that finds the scheduler holding the
    queue's lock records the wait, inside its ``serve.submit``; one that
    finds the lock free records none."""
    params = init_autoencoder(jax.random.PRNGKey(0), _CFG)
    server = StreamServer(StreamingAnomalyEngine(params, _CFG, batch=1),
                          ServerConfig(max_coalesce=2))
    chunk = np.zeros((6, 1), np.float32)
    telemetry.reset()
    server.submit(0, chunk)
    assert "serve.submit_lock" not in telemetry.snapshot()["spans"]
    producer = threading.Thread(target=server.submit, args=(1, chunk))
    with server._cond:
        producer.start()
        threading.Event().wait(0.05)
    producer.join(10)
    spans = telemetry.snapshot()["spans"]
    assert spans["serve.submit_lock"]["count"] == 1
    assert spans["serve.submit_lock"]["p50_us"] >= 40000
    assert spans["serve.submit"]["count"] == 2
    assert spans["serve.submit"]["max_us"] >= spans["serve.submit_lock"][
        "max_us"]
    telemetry.reset()
