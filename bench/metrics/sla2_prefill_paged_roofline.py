"""The paged prefill kernel's least time on the chip over its time in
the trace, in percent: every prefill chunk of the traced window, one call
per layer (bench/costs/sla2_prefill_paged)."""
from bench.costs import sla2_prefill_paged as C


def read(rec):
    k = (rec.trace or {}).get("kernels", {}).get(C.KERNEL)
    if not k or not k["count"]:
        return None
    cfg = rec.config
    dims = dict(heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], d=cfg["head_dim"])
    ideal = sum(C.ideal_s(*C.per_call(*st["prefill"], **dims), rec.peaks)
                for st in rec.extra["part_steps"] if st["prefill"])
    return 100.0 * ideal * cfg["num_hidden_layers"] / k["time_s"]
