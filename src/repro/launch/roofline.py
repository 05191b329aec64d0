"""Three-term roofline analysis from the dry-run's compiled artifacts.

For every (arch x shape x mesh) JSON produced by launch/dryrun.py:

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s          [s]
    memory term     = HLO_bytes_per_device / HBM_bw               [s]
    collective term = collective_bytes_per_device / link_bw       [s]

(cost_analysis numbers are per-partition on an SPMD module — verified by
calibration in tests/test_distributed.py — so no extra /chips.)

Also reported per cell:
    MODEL_FLOPS        = 6*N*D (train) or 2*N*D (serve), N_active for MoE
    useful-flops ratio = MODEL_FLOPS / (HLO_FLOPs * chips)
    dominant term + one-line 'what would move it' note

Usage:
    PYTHONPATH=src python -m repro.launch.roofline \
        [--dry-dir results/dryrun] [--out results/roofline.md]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.launch.mesh import V5E, chip_peaks

# tokens-per-step and step kind per shape cell
from repro.configs import SHAPES
from repro.configs.wan_dit_1_3b import DIT_SHAPES

V5E_PEAKS = chip_peaks(V5E)     # the chip these rooflines model


def arch_param_counts(arch: str) -> dict:
    """(total, active) param counts from the abstract init (no allocation)."""
    import jax
    from repro.configs import get_config
    from repro.models.api import build_model
    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    total = 0
    expert_total = 0
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    for path, leaf in leaves:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if "moe/w_in" in pstr or "moe/w_out" in pstr:
            expert_total += n
    active = total
    moe = getattr(cfg, "moe", None)
    if moe is not None and expert_total:
        active = total - expert_total \
            + expert_total * (moe.top_k / moe.num_experts)
    return {"total": total, "active": active}


_NOTES = {
    "compute": ("compute-bound: raise MXU utilisation — larger per-chip "
                "tiles (bigger microbatch or less model parallelism), int8 "
                "QAT path (2x MXU), or cut redundant HLO flops (remat "
                "policy)"),
    "memory": ("HBM-bound: fuse/eliminate intermediate materialisations "
               "(attention gather width q_chunk, loss chunking), keep "
               "activations bf16, shard the sequence (SP) to cut per-chip "
               "working set"),
    "collective": ("collective-bound: reshard to cut cross-chip traffic — "
                   "fewer tensor-parallel boundaries per block, overlap "
                   "collectives with compute (async), int8-compress the "
                   "pod-crossing gradient reduction"),
}


# archs whose recurrent inner loops stay rolled even in accounting mode:
# their HLO flops undercount; the roofline substitutes the analytic floor
# max(HLO, 2*N_active*tokens*(3 if train else 1)) and flags the row.
ANALYTIC_SSM = {"xlstm_350m"}


def analyze_cell(rec: dict, counts: dict) -> dict:
    shapes = DIT_SHAPES if rec["arch"] == "wan_dit_1_3b" else SHAPES
    sh = shapes[rec["shape"]]
    chips = rec["devices"]
    flops_dev = max(rec["cost"]["flops"], 0.0)
    # HBM traffic model: arguments read once + outputs written once +
    # HBM-resident temps written+read.  cost_analysis' "bytes accessed"
    # counts every fused intermediate (VMEM/register traffic on TPU) and
    # over-states HBM by orders of magnitude; it is kept in the JSON as
    # hlo_logical_bytes for reference.
    m = rec["memory"]
    bytes_dev = (m.get("argument_bytes", 0) + m.get("output_bytes", 0)
                 + 2 * m.get("temp_bytes", 0))
    coll_dev = max(rec["collectives"]["total_bytes"], 0.0)
    analytic = False
    if rec["arch"] in ANALYTIC_SSM:
        mode0 = sh["mode"]
        toks = (sh["seq_len"] * sh["global_batch"]
                if mode0 != "decode" else sh["global_batch"])
        passes = 3.0 if mode0 == "train" else 1.0
        floor = 2.0 * counts["active"] * toks * passes / chips
        if floor > flops_dev:
            flops_dev, analytic = floor, True

    t_compute = flops_dev / V5E_PEAKS.flops_bf16
    t_memory = bytes_dev / V5E_PEAKS.hbm_bw
    t_coll = coll_dev / V5E_PEAKS.ici_bw
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)

    mode = sh["mode"]
    if mode == "train":
        tokens = sh["seq_len"] * sh["global_batch"]
        model_flops = 6.0 * counts["active"] * tokens
    elif mode == "prefill":
        tokens = sh["seq_len"] * sh["global_batch"]
        model_flops = 2.0 * counts["active"] * tokens
    else:  # decode: one token per sequence
        tokens = sh["global_batch"]
        model_flops = 2.0 * counts["active"] * tokens
    useful = model_flops / max(flops_dev * chips, 1.0)

    # roofline fraction: how close the dominant term is to being the ONLY
    # cost => step_time ~= max(terms); efficiency = ideal_compute / max
    ideal = model_flops / chips / V5E_PEAKS.flops_bf16
    frac = ideal / max(max(terms.values()), 1e-30)

    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh", "devices")},
        "analytic_flops": analytic,
        "terms_s": {k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_global": flops_dev * chips,
        "hlo_logical_bytes": rec["cost"]["bytes_accessed"],
        "useful_flops_ratio": round(useful, 4),
        "roofline_fraction": round(frac, 4),
        "peak_gib_per_dev": round(
            rec["memory"]["peak_bytes_per_device"] / 2 ** 30, 2),
        "note": _NOTES[dominant],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-dir", default="results/dryrun")
    ap.add_argument("--out", default="results/roofline.md")
    ap.add_argument("--json-out", default="results/roofline.json")
    args = ap.parse_args()

    recs = []
    for path in sorted(glob.glob(os.path.join(args.dry_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") == "ok":
            recs.append(rec)
    counts_cache: dict[str, dict] = {}
    rows = []
    for rec in recs:
        arch = rec["arch"]
        if arch not in counts_cache:
            counts_cache[arch] = arch_param_counts(arch)
        rows.append(analyze_cell(rec, counts_cache[arch]))

    os.makedirs(os.path.dirname(args.json_out) or ".", exist_ok=True)
    with open(args.json_out, "w") as f:
        json.dump(rows, f, indent=1)

    lines = [
        "| arch | shape | mesh | compute s | memory s | collective s | "
        "dominant | useful-flops | roofline-frac | GiB/dev |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        t = r["terms_s"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {t['compute']:.4g} | {t['memory']:.4g} "
            f"| {t['collective']:.4g} | **{r['dominant']}** "
            f"| {r['useful_flops_ratio']:.3f} "
            f"| {r['roofline_fraction']:.3f} | {r['peak_gib_per_dev']} |")
    table = "\n".join(lines)
    with open(args.out, "w") as f:
        f.write(table + "\n")
    print(table)


if __name__ == "__main__":
    main()
