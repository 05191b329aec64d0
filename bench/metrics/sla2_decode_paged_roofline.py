"""The paged SLA2 decode kernel's least time on the chip over its time
in the trace, in percent: every decode row of every engine step in the
traced window, one call per layer (bench/costs/sla2_decode_paged)."""
from bench.costs import sla2_decode_paged as C


def read(rec):
    k = (rec.trace or {}).get("kernels", {}).get(C.KERNEL)
    if not k or not k["count"]:
        return None
    cfg = rec.config
    dims = dict(heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], d=cfg["head_dim"],
                block_k=cfg["sla2"]["block_k"], k_sel=rec.extra["k_sel"])
    ideal = 0.0
    for st in rec.extra["part_steps"]:
        if st["decode_rows"]:
            ops = nbytes = 0.0
            for t in st["decode_rows"]:
                o, b = C.per_row(t, **dims)
                ops, nbytes = ops + o, nbytes + b
            ideal += C.ideal_s(ops, nbytes, rec.peaks)
    return 100.0 * ideal * cfg["num_hidden_layers"] / k["time_s"]
