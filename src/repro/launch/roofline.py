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


# ---------------------------------------------------------------------------
# Quantized page-pool sizing (EngineConfig.kv_quant)
# ---------------------------------------------------------------------------
# Bytes per stored K/V element by pool storage mode.  'none' is the bf16
# serving baseline; int8/fp8 pools store 1-byte codes plus one fp32 scale
# per (page, kv head, token row) — the scale overhead is Dh elements'
# worth of f32 per row, i.e. 4/Dh relative, ~3% at Dh=128.

KV_QUANT_BYTES = {"none": 2, "int8": 1, "fp8": 1}


def kv_page_bytes(hkv: int, page_tokens: int, head_dim: int,
                  kv_quant: str = "none", *, sla2: bool = False) -> int:
    """HBM bytes of ONE physical page of ONE layer's pool.

    K + V codes (2 * hkv * page_tokens * head_dim elements) at the
    storage width, plus — when quantized — the per-row fp32 scales
    (2 * hkv * page_tokens).  ``sla2=True`` adds the per-page pooled
    router key (hkv * head_dim codes + hkv fp32 scales when quantized)."""
    el = KV_QUANT_BYTES[kv_quant]
    n_kv = 2 * hkv * page_tokens * head_dim
    total = n_kv * el
    if kv_quant != "none":
        total += 2 * hkv * page_tokens * 4          # k_scale + v_scale rows
    if sla2:
        total += hkv * head_dim * el                # pooled router key
        if kv_quant != "none":
            total += hkv * 4                        # pooled_scale
    return total


def mla_latent_page_bytes(latent_dim: int, page_tokens: int,
                          kv_quant: str = "none") -> int:
    """HBM bytes of ONE physical latent page of ONE MLA layer's pool
    (models.mla.init_mla_paged_cache): the compressed latent rows
    (page_tokens * latent_dim codes — stored ONCE, not as separate K and
    V) plus the per-page pooled router latent; quantized pools add one
    fp32 scale per token row and per pooled key, unquantized pools keep
    the pooled key in fp32.  Compare against ``kv_page_bytes(hkv=heads,
    ...)`` for the dense-cache equivalent — the paged-MLA memory win the
    fig14 family benchmark plots.  These are the latent's own bytes: the
    pool stores each row padded to whole 128-lane tiles (models/mla.py),
    640 values for DeepSeek-V2's 576."""
    el = KV_QUANT_BYTES[kv_quant]
    total = page_tokens * latent_dim * el           # k_pages rows
    if kv_quant != "none":
        total += page_tokens * 4                    # k_scale
        total += latent_dim * el + 4                # pooled codes + scale
    else:
        total += latent_dim * 4                     # pooled key kept f32
    return total


def pool_pages_for_hbm(budget_bytes: float, n_layers: int, hkv: int,
                       page_tokens: int, head_dim: int,
                       kv_quant: str = "none", *, sla2: bool = False) -> int:
    """Physical pages an HBM budget holds when every layer keeps a pool
    (the serving allocator sizes all layers' pools to the same page
    count)."""
    per_page = n_layers * kv_page_bytes(hkv, page_tokens, head_dim,
                                        kv_quant, sla2=sla2)
    return int(budget_bytes // per_page)


def sharded_pool_slots(n_hosts: int, hbm_per_host: float,
                       weight_bytes: float, n_layers: int, hkv: int,
                       page_tokens: int, head_dim: int,
                       pages_per_slot: int, kv_quant: str = "none", *,
                       sla2: bool = False) -> dict:
    """Page-pool capacity of an ``n_hosts`` serving mesh — the
    fig13_mesh_scaling model.

    Every host keeps a full weight replica (serving params shard the
    model axis only — ``distributed.sharding.serving_param_specs`` — and
    the host mesh has model=1) and gives the rest of its HBM to its page
    pool shard (``cache_specs``: page axis over all mesh axes).  Total
    concurrent slots therefore scale with hosts at fixed per-slot page
    demand: slots = n_hosts * pages_per_host // pages_per_slot."""
    per_host_budget = max(0.0, hbm_per_host - weight_bytes)
    pages_host = pool_pages_for_hbm(per_host_budget, n_layers, hkv,
                                    page_tokens, head_dim, kv_quant,
                                    sla2=sla2)
    total_pages = n_hosts * pages_host
    return {"hosts": n_hosts, "pages_per_host": pages_host,
            "total_pages": total_pages,
            "slots": total_pages // max(1, pages_per_slot)}


# ---------------------------------------------------------------------------
# Diffusion attention traffic (serve/diffusion.DiffusionEngine hot loop)
# ---------------------------------------------------------------------------

def diffusion_attention_bytes(n: int, head_dim: int, *,
                              sparsity: float = 0.0, method: str = "full",
                              block_q: int = 128, block_k: int = 64,
                              el_bytes: int = 2) -> float:
    """HBM bytes of ONE bidirectional self-attention forward per head at
    ``n`` latent tokens — the denoise-step hot loop modeled by
    benchmarks/fig12_diffusion.py.

    All methods are flash-style (no N^2 materialisation): Q is read once
    and O written once.  'full' additionally streams all of K and V;
    the sparse branch streams only the selected ``(1 - sparsity)``
    fraction of K/V tiles; sla/sla2 add one full K/V pass for the linear
    states plus the phi(Q) side, and every routed method pays the router:
    the block-pooled K (n/block_k rows) and the (n/block_q, n/block_k)
    score/Top-k map, recomputed every denoise step."""
    qo = 2 * n * head_dim * el_bytes                 # Q read + O write
    if method == "full":
        return qo + 2 * n * head_dim * el_bytes      # all of K + V
    kv = (1.0 - sparsity) * 2 * n * head_dim * el_bytes
    router = (n / block_k) * head_dim * el_bytes \
        + (n / block_q) * (n / block_k) * 4
    total = qo + kv + router
    if method in ("sla", "sla2"):
        total += 3 * n * head_dim * el_bytes         # linear K,V pass + phiQ
    return total


def attention_roofline_s(flops: float, bytes_: float) -> float:
    """max(compute, memory) seconds on one v5e.  Quantized-MXU speedup
    is modeled upstream by ``benchmarks.common.attention_flops``'s
    ``quant_speed`` (it divides the sparse-branch FLOPs), so the peaks
    here stay bf16."""
    return max(flops / V5E_PEAKS.flops_bf16, bytes_ / V5E_PEAKS.hbm_bw)


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
