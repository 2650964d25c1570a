"""Model step: the decode steps' share of their roofline in the window.

Each step's least time is the larger of its bytes over the chip's HBM
bandwidth and its FLOPs over its peak: the weights read once, every live
slot's SSD state and conv window read and written, its KV read at its
length and one position written (``granite_hybrid.step_work``), summed
over the steps, over the device time of the step modules in the trace."""

from perfbench import peaks


def read(run):
    seconds = run.counts.get("step_device_s", 0.0)
    if seconds <= 0 or not run.counts.get("steps"):
        return None
    flops, nbytes = run.cell.model.step_work(
        run.cell.config, run.counts["steps"], run.counts["slot_steps"],
        run.counts["context"])
    share, _ = peaks.roofline_share(flops, nbytes, seconds, run.peak,
                                    dtype="bf16")
    return share
