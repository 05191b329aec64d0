"""The sparse-branch kernel's least time on the chip over its time in the
trace, in percent, over the DiT denoise steps of the traced window: each
occupied slot's heads are rows of bench/costs/sla2_sparse_fwd."""
from bench.costs import sla2_sparse_fwd as C


def read(rec):
    k = (rec.trace or {}).get("kernels", {}).get(C.KERNEL)
    if not k or not k["count"]:
        return None
    cfg, s = rec.config, rec.config["sla2"]
    n = cfg["latent_tokens"]
    k_sel = max(1, round(s["k_frac"] * (n // s["block_k"])))
    ops, nbytes = C.per_row(n, cfg["head_dim"], s["block_k"], k_sel)
    rows = sum(st["occupancy"] for st in rec.extra["part_steps"]) \
        * cfg["num_heads"] * cfg["num_layers"]
    return 100.0 * rows * C.ideal_s(ops, nbytes, rec.peaks) / k["time_s"]
