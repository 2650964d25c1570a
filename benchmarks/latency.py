"""Shared latency-summary helper for benchmark scripts and the serve CLI.

One histogram implementation serves every latency consumer in the repo —
``repro.latency.LatencyHistogram`` (fixed geometric us bins,
O(1) record, p50/p99/max summaries).  The ``StreamServer`` records into
it natively; this module re-exports it for the benchmark scripts (which
live outside ``src/``) and adds the one benchmark-side convenience:
turning a summary into ``(name, us, derived)`` rows for
``benchmarks/run.py``'s CSV/JSON contract (e.g. ``serve.p50_us`` /
``serve.p99_us``).
"""

from __future__ import annotations

from repro.latency import LatencyHistogram

__all__ = ["LatencyHistogram", "latency_rows", "record_latencies"]


def record_latencies(us_values) -> LatencyHistogram:
    """A histogram pre-filled from an iterable of us samples."""
    hist = LatencyHistogram()
    hist.record_many(us_values)
    return hist


def latency_rows(
    prefix: str, hist: LatencyHistogram, percentiles=(50, 99)
) -> list[tuple]:
    """Benchmark rows for a histogram: ``{prefix}.p{q}_us`` per requested
    percentile, each carrying count/mean/max in the derived field."""
    derived = (
        f"count={hist.count}|mean_us={hist.mean_us:.1f}|"
        f"max_us={hist.max_us:.1f}"
    )
    return [
        (f"{prefix}.p{q}_us", hist.percentile(q), derived)
        for q in percentiles
    ]
