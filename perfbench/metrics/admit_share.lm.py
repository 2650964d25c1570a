"""Slot engine: percent of the window's seconds the host spent in the
program's ``lm.admit`` spans (an admission's prompt copy, prefill
dispatch and state write, as dispatched)."""


def read(run):
    total = run.counts.get("lm.admit.total_s")
    if total is None or not run.trace.device_ops:
        return None
    return 100.0 * total / run.window_s
