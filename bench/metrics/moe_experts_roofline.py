"""The expert kernel's least time on the chip over its time in the trace,
in percent, over the engine steps of the traced window
(bench/costs/moe_experts).  A step's work is its expert counters
(ServeEngine.stats moe_held_rows and moe_active_experts, summed over the
step's expert layers and programs); every call is bound by the weights'
bytes, so the least time of a step's calls is that of their sum.  None
where the engine keeps no such counters or the trace holds no such
kernel."""
from bench.costs import moe_experts as C


def read(rec):
    k = (rec.trace or {}).get("kernels", {}).get(C.KERNEL)
    steps = rec.extra["part_steps"]
    if not k or not k["count"] or not steps \
            or "moe_held_rows" not in steps[0]:
        return None
    cfg = rec.config
    dims = dict(d=cfg["hidden_size"], ff=cfg["moe_intermediate_size"])
    ideal = sum(C.ideal_s(*C.per_calls(st["moe_held_rows"],
                                       st["moe_active_experts"], **dims),
                          rec.peaks) for st in steps)
    return 100.0 * ideal / k["time_s"]
