"""Kernel layer: the step kernel's share of its roofline in the window.

The least time for the encoder's own work on the real (unpadded) stream
rows at published widths (``kernel_work``), over the device time of the
``lstm_stack_step`` events in the trace."""

from perfbench import peaks, sut


def read(run):
    lo, hi = run.lo, run.hi
    seconds = run.trace.kernel_s(sut.STEP_KERNEL, lo, hi)
    calls = run.trace.kernel_count(sut.STEP_KERNEL, lo, hi)
    if seconds <= 0 or not run.counts.get("step_row_steps"):
        return None
    flops, nbytes = run.cell.model.kernel_work(
        run.cell.config, "encoder", run.counts["step_row_steps"],
        run.counts["step_rows"], calls)
    share, _ = peaks.roofline_share(flops, nbytes, seconds, run.peak)
    return share
