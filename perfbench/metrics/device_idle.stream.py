"""Device layer: percent of the measured window in which no operation ran
on the chip (1 - union of device-op intervals over the window)."""


def read(run):
    if not run.trace.device_ops:
        return None
    return 100.0 * (1.0 - run.trace.busy_s(run.lo, run.hi) / run.window_s)
