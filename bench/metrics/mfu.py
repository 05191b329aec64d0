"""Model FLOPs of the work the engine completed in the traced part of the
window, over that time and the chip's bf16 peak, in percent.  The FLOPs
come from the system's cost model (bench/costs/model_<system>.py), the
time from the host clock."""


def read(rec):
    steps = rec.extra["part_steps"]
    if not steps or rec.peaks is None:
        return None
    flops = rec.extra["flops_fn"](steps)
    return 100.0 * flops / (rec.extra["part_s"] * rec.peaks["flops_bf16"])
