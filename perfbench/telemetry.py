"""The program's own spans, read in-process from its recorder
(``repro.serve.telemetry``).

The recorder keeps per-name histograms for the whole process: the
warm-up and the lead-in of the same traffic fall in them too, against
thousands of records in the window, so the metrics that read it are
medians.  A program without the recorder, or a span with no records,
reads ``None``; so does a run without a device trace, where the spans
would time the CPU interpreter's work and not the chip's host.
"""

from __future__ import annotations


def snapshot() -> dict | None:
    """The recorder's ``snapshot()``, or ``None`` where the program has no
    recorder."""
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    return telemetry.snapshot()


def p50_us(run, name: str) -> float | None:
    """The median duration of span ``name`` in microseconds, where the run
    traced a device and the span has records."""
    if not run.trace.device_ops:
        return None
    snap = snapshot()
    stat = snap and snap["spans"].get(name)
    if not stat or not stat["count"]:
        return None
    return stat["p50_us"]
