"""Model step: the model's own FLOPs per window at published widths, times
the windows scored in the window, over the window's seconds and the chip's
peak, in percent."""

from perfbench import peaks


def read(run):
    windows = run.counts.get("windows_scored", 0)
    if not windows or not run.trace.device_ops:
        return None
    flops = windows * run.cell.model.model_flops(run.cell.config)
    return peaks.mfu(flops, run.window_s, run.peak, run.chips)
