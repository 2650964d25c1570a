"""The readers of the program's spans, on synthetic recorder snapshots."""

from types import SimpleNamespace

import pytest

import _pb
from perfbench import harness
from perfbench import telemetry as pbt


def _stat(p50_us):
    return {"count": 10, "total_s": 10 * p50_us * 1e-6, "p50_us": p50_us,
            "p99_us": 3 * p50_us, "max_us": 5 * p50_us,
            "self_p50_us": p50_us / 2}


SNAP = {
    "spans": {
        "serve.queue_wait": _stat(41.0),
        "engine.step": _stat(180.0),
        "engine.finish_sync": _stat(950.0),
        "engine.score_host": _stat(2300.0),
        "engine.push_many": _stat(1500.0),
    },
    "counters": {"engine.states_created": 12},
}

#: metric -> the value it reads from SNAP
READS = [
    ("finish_sync_us.stream", "engine.finish_sync", 950.0),
    ("score_gap_ms.archive", "engine.score_host", 2.3),
]


def _run(device_ops=True):
    ops = {0: [("lstm_stack_step.1", 0, 10)]} if device_ops else {}
    return SimpleNamespace(trace=SimpleNamespace(device_ops=ops),
                           counts={}, lo=0, hi=100)


def _reader(metric):
    return harness.load_module(
        _pb.ROOT / "perfbench" / "metrics" / f"{metric}.py")


@pytest.mark.parametrize("metric,span,want", READS)
def test_each_reader_reads_its_span_median(monkeypatch, metric, span, want):
    monkeypatch.setattr(pbt, "snapshot", lambda: SNAP)
    assert _reader(metric).read(_run()) == pytest.approx(want)


@pytest.mark.parametrize("metric,span,want", READS)
def test_each_reader_is_none_without_its_span(monkeypatch, metric, span,
                                              want):
    spans = {k: v for k, v in SNAP["spans"].items() if k != span}
    monkeypatch.setattr(pbt, "snapshot",
                        lambda: {"spans": spans, "counters": {}})
    assert _reader(metric).read(_run()) is None
    spans[span] = dict(_stat(1.0), count=0)
    assert _reader(metric).read(_run()) is None


@pytest.mark.parametrize("metric,span,want", READS)
def test_each_reader_is_none_without_the_recorder_or_a_device_trace(
        monkeypatch, metric, span, want):
    monkeypatch.setattr(pbt, "snapshot", lambda: None)
    assert _reader(metric).read(_run()) is None
    monkeypatch.setattr(pbt, "snapshot", lambda: SNAP)
    assert _reader(metric).read(_run(device_ops=False)) is None


def test_snapshot_reads_the_programs_recorder():
    from repro.serve import telemetry

    with telemetry.span("pbtest.span"):
        pass
    snap = pbt.snapshot()
    assert snap["spans"]["pbtest.span"]["count"] >= 1
