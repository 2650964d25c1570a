"""Model step: the model's own FLOPs in the window, the decoded tokens'
and the admitted prompts', at published widths
(``granite_hybrid.model_flops``), over the window's seconds and the chip's
bf16 peak, in percent."""

from perfbench import peaks


def read(run):
    c = run.counts
    if not c.get("tokens") or not run.trace.device_ops:
        return None
    model, cfg = run.cell.model, run.cell.config
    flops = (model.model_flops(cfg, c["slot_steps"], c["context"],
                               c["slot_steps"])
             + model.model_flops(cfg, c["prefill_tokens"],
                                 c["prefill_context"], c["admitted"]))
    return peaks.mfu(flops, run.window_s, run.peak, run.chips, dtype="bf16")
