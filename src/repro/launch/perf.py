import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Perf hillclimb driver: re-lower a dry-run cell under a named variant and
report the roofline-term deltas vs the saved baseline.

    PYTHONPATH=src python -m repro.launch.perf --arch wan_dit_1_3b \
        --shape train_32k --variant fused

Variants (the hypothesis behind each is logged in EXPERIMENTS.md §Perf):
    fused        single-pass sparse+linear gather (fuse_branches=True)
    remat_none   no activation rematerialisation (memory-for-flops trade)
    no_sp        disable sequence parallelism
    mb<k>        k gradient-accumulation microbatches (train cells)
    kfrac<val>   router keep-fraction, e.g. kfrac0.03
    bk128        block_k=128 (MXU-width kv tiles)
    qchunk<k>    gather chunk width
    noquant      disable the INT8 QAT forward
"""


import argparse
import json

from repro.launch.dryrun import run_cell


def variant_kwargs(variant: str) -> dict:
    if variant == "baseline" or not variant:
        return {}
    if variant == "fused":
        return {"cfg_overrides": {"fuse_branches": True}}
    if variant == "remat_none":
        return {"cfg_overrides": {"remat": "none"}}
    if variant == "no_sp":
        return {"sp": False}
    if variant.startswith("mb"):
        return {"microbatches": int(variant[2:])}
    if variant.startswith("kfrac"):
        return {"cfg_overrides": {"k_frac": float(variant[5:])}}
    if variant == "bk128":
        return {"cfg_overrides": {"block_k": 128}}
    if variant.startswith("qchunk"):
        return {"cfg_overrides": {"q_chunk": int(variant[6:])}}
    if variant == "noquant":
        return {"cfg_overrides": {"quant_bits": "none"}}
    raise ValueError(variant)


def summarize(rec: dict) -> dict:
    from repro.launch.mesh import V5E, chip_peaks
    peaks = chip_peaks(V5E)
    if rec["status"] != "ok":
        return {"status": rec["status"], "error": rec.get("error")}
    c = rec["cost"]
    return {
        "compute_s": c["flops"] / peaks.flops_bf16,
        "memory_s": c["bytes_accessed"] / peaks.hbm_bw,
        "collective_s": rec["collectives"]["total_bytes"] / peaks.ici_bw,
        "peak_gib": rec["memory"]["peak_bytes_per_device"] / 2 ** 30,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--variant", required=True)
    ap.add_argument("--out", default="results/perf")
    args = ap.parse_args()

    base = run_cell(args.arch, args.shape, args.mesh,
                    save_dir="results/dryrun")   # cached baseline
    rec = run_cell(args.arch, args.shape, args.mesh, save_dir=args.out,
                   force=True, variant=args.variant,
                   **variant_kwargs(args.variant))
    b, v = summarize(base), summarize(rec)
    print(json.dumps({"baseline": b, args.variant: v}, indent=1))
    if rec["status"] == "ok" and base["status"] == "ok":
        for key in ("compute_s", "memory_s", "collective_s", "peak_gib"):
            if b[key] > 0:
                print(f"{key:14s} {b[key]:10.4g} -> {v[key]:10.4g} "
                      f"({100 * (v[key] / b[key] - 1):+7.1f}%)")


if __name__ == "__main__":
    main()
