"""Kernel layer: the wavefront kernel's share of its roofline in the
window, on the archive path, where each ``score`` call runs it once for
the encoder and once for the decoder over every window of the batch.

The least time for both segments' own work at published widths
(``kernel_work``), over the device time of the ``lstm_stack_wavefront``
events in the trace."""

from perfbench import peaks, sut


def read(run):
    lo, hi = run.lo, run.hi
    seconds = run.trace.kernel_s(sut.WAVEFRONT_KERNEL, lo, hi)
    calls = run.trace.kernel_count(sut.WAVEFRONT_KERNEL, lo, hi)
    windows = run.counts.get("windows_scored", 0)
    if seconds <= 0 or not windows:
        return None
    cfg, t = run.cell.config, run.cell.config["timesteps"]
    flops = nbytes = 0
    for segment in ("encoder", "decoder"):
        f, b = run.cell.model.kernel_work(
            cfg, segment, windows * t, windows, calls // 2)
        flops, nbytes = flops + f, nbytes + b
    share, _ = peaks.roofline_share(flops, nbytes, seconds, run.peak)
    return share
