"""Figure 13 (beyond paper): sharded paged serving — per-host HBM to
total-concurrent-slots scaling model for the mesh engine (PR 9).

The serving mesh replicates the weights per host (serving params shard
the model axis only — ``distributed.sharding.serving_param_specs`` — and
``launch.mesh.make_host_mesh`` builds an (n, 1) host mesh) and shards the
page pool's page axis across hosts (``cache_specs``).  Capacity
therefore scales with hosts at fixed per-slot page demand:

  pages_per_host = pool_pages_for_hbm(HBM - weight_replica_bytes, ...)
  slots(n)       = n * pages_per_host // pages_per_slot(ctx)

Sections (all modeled — this container has no multi-host TPU):

  (1) slots-vs-hosts curve on the qwen3-14b serving geometry for each
      pool storage mode (bf16 / int8 / fp8 pages) at three context
      lengths, from ``launch.roofline.sharded_pool_slots``.  Asserted
      monotone non-decreasing in hosts (the acceptance gate); the global
      allocator pools page remainders across hosts, so the curve is in
      fact super-linear: slots(n) >= n * slots(1).
  (2) reshard-cost model: when a host dies, the engine rebuilds the pool
      on the survivors (serve/engine._reshard_after_failure) and the
      preempted slots' private pages are refilled by swap-in or
      recompute; we model the swap-in path as moving those pages over
      ICI (bytes / V5E_PEAKS.ici_bw) per storage mode.

Both smoke and full runs refresh the top-level BENCH_mesh.json artifact
(the acceptance criterion is that it records the modeled curve).
"""
from __future__ import annotations

import argparse
import json
import os

from benchmarks.common import markdown_table, save_result
from repro.launch.mesh import V5E, chip_peaks
from repro.launch.roofline import kv_page_bytes, sharded_pool_slots

V5E_PEAKS = chip_peaks(V5E)     # the chip these rooflines model

# qwen3-14b serving geometry (matches fig6/fig9/fig11)
LAYERS, HKV, N_REP, DH = 40, 8, 5, 128
BK = 64                                    # tokens per page
N_PARAMS = 14.8e9                          # qwen3-14b
WEIGHT_BYTES = N_PARAMS                    # int8 serving replica per host
HOSTS = (1, 2, 4, 8, 16)
CONTEXTS = (8192, 32768, 131072)
MODES = ("none", "int8", "fp8")

TOP_LEVEL_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                              "BENCH_mesh.json")


def modeled_curve() -> dict:
    """slots(n_hosts) per pool storage mode and context length."""
    rows = []
    for mode in MODES:
        for ctx in CONTEXTS:
            row = {"kv_quant": mode, "ctx": ctx}
            for n in HOSTS:
                cap = sharded_pool_slots(
                    n, V5E_PEAKS.hbm_bytes, WEIGHT_BYTES, LAYERS, HKV, BK, DH,
                    pages_per_slot=ctx // BK, kv_quant=mode, sla2=True)
                row[f"slots_h{n}"] = cap["slots"]
                if n == 1:
                    row["pages_per_host"] = cap["pages_per_host"]
            rows.append(row)
    return {"rows": rows}


def modeled_reshard() -> dict:
    """Failure-recovery cost: one dead host out of n loses its pool
    shard; refilling the preempted slots' pages from the swap store
    streams them over ICI onto the surviving hosts."""
    rows = []
    for mode in MODES:
        page_b = LAYERS * kv_page_bytes(HKV, BK, DH, mode, sla2=True)
        for n in (4, 8, 16):
            cap = sharded_pool_slots(
                n, V5E_PEAKS.hbm_bytes, WEIGHT_BYTES, LAYERS, HKV, BK, DH,
                pages_per_slot=1, kv_quant=mode, sla2=True)
            lost_pages = cap["pages_per_host"]
            rows.append({
                "kv_quant": mode, "hosts": n,
                "lost_pages": lost_pages,
                "lost_gib": round(lost_pages * page_b / 2 ** 30, 2),
                "swap_in_ms": round(
                    lost_pages * page_b / V5E_PEAKS.ici_bw * 1e3, 1),
            })
    return {"rows": rows}


def run(smoke: bool = False) -> dict:
    curve = modeled_curve()
    monotone = all(
        all(row[f"slots_h{a}"] <= row[f"slots_h{b}"]
            for a, b in zip(HOSTS, HOSTS[1:]))
        for row in curve["rows"])
    superlinear = all(
        row[f"slots_h{n}"] >= n * row["slots_h1"]
        for row in curve["rows"] for n in HOSTS)
    payload = {
        "geometry": {"layers": LAYERS, "hkv": HKV, "n_rep": N_REP,
                     "dh": DH, "page_tokens": BK,
                     "hbm_per_host_gib": V5E_PEAKS.hbm_bytes / 2 ** 30,
                     "weight_replica_gib": round(WEIGHT_BYTES / 2 ** 30, 2)},
        "hosts": list(HOSTS),
        "modeled_slots_vs_hosts": curve,
        "modeled_reshard": modeled_reshard(),
        # acceptance: total concurrent slots never drop when hosts are
        # added (replica weights + page-axis-sharded pool; the global
        # allocator pools per-host page remainders => super-linear)
        "acceptance_monotone": monotone,
        "superlinear_in_hosts": superlinear,
    }
    save_result("fig13_mesh_scaling", payload)
    with open(TOP_LEVEL_JSON, "w") as f:
        json.dump(payload, f, indent=1)
    print(markdown_table(curve["rows"],
                         ["kv_quant", "ctx", "pages_per_host"]
                         + [f"slots_h{n}" for n in HOSTS]))
    print()
    print(markdown_table(payload["modeled_reshard"]["rows"],
                         ["kv_quant", "hosts", "lost_pages", "lost_gib",
                          "swap_in_ms"]))
    print(f"\nmonotone in hosts: {monotone}; "
          f"superlinear (remainder pooling): {superlinear}")
    assert monotone, "slots-vs-hosts curve must be monotone"
    assert superlinear, "global allocator must not lose pages to shards"
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="same modeled tables (everything here is "
                         "modeled); kept for run.py/CI symmetry")
    args = ap.parse_args()
    run(smoke=args.smoke)
