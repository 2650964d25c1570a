"""Slot engine: mean live slots per decode step in the window, from the
program's counter ``lm.slot_steps`` (live slots summed over steps) over
its ``lm.step`` spans."""


def read(run):
    steps = run.counts.get("lm.step.count")
    if not steps or "lm.slot_steps" not in run.counts:
        return None
    return run.counts["lm.slot_steps"] / steps
