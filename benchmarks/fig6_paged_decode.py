"""Figure 6 (beyond paper): paged decode step latency & throughput —
fused page-table kernel vs gather decode vs static dense decode.

Three sections, same methodology split as fig4/fig5 (no TPU in this
container, so compiled-kernel wall-clock is out):

  (1) MODELED: v5e roofline of one decode step on the qwen3-14b serving
      geometry.  Decode is bandwidth-bound, so the story is bytes moved
      per step:
        * fused   — the Pallas kernel reads each routed K/V page from the
                    pool exactly ONCE (scalar-prefetched page table drives
                    the DMA), plus router pooled keys and the linear-branch
                    totals; the linear correction and alpha combine ride
                    the same pass.
        * gather  — the jnp reference materialises a (B, Hkv, K_sel, bk, Dh)
                    copy of the routed pages (read + write), then the
                    softmax / phi(k) / PV einsum chain re-reads the copies:
                    ~3x the page bytes of the fused kernel.
        * static  — dense decode over a max_len cache reads the FULL
                    context every step (the StaticWaveEngine regime).
  (2) MEASURED KERNEL SMOKE (interpret mode, tiny shape): the fused kernel
      and the gather reference run on the same routed state; asserts
      parity (fp32 tight, int8 within quantization noise) and records the
      CPU wall times.  This is the CI guard that the shipped kernel both
      runs and agrees — interpret-mode absolute times are NOT comparable.
  (3) MEASURED ENGINE (CPU proxy, skipped with --smoke): tokens/sec of a
      mixed-length workload through ServeEngine with the gather path vs
      StaticWaveEngine — tracks the serving trajectory on real executions.

Results go to results/benchmarks/fig6_paged_decode.json AND to the
top-level BENCH_paged_decode.json so the perf trajectory is tracked
across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import markdown_table, save_result
from repro.launch.mesh import V5E, chip_peaks

V5E_PEAKS = chip_peaks(V5E)     # the chip these rooflines model

# qwen3-14b serving geometry
LAYERS, HKV, N_REP, DH = 40, 8, 5, 128
BK = 64                                    # tokens per page
K_FRAC = 0.03                              # 97% block sparsity
BF16, F32 = 2, 4

BATCHES = (1, 4, 8, 16, 32)
CONTEXTS = (8192, 32768, 131072)

TOP_LEVEL_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                              "BENCH_paged_decode.json")


def modeled_step(batch: int, ctx: int, method: str,
                 kv_quant: str = "none") -> float:
    """Roofline seconds for ONE decode step over all layers on one v5e.

    Decode is bandwidth-bound at these shapes, so the methods differ in
    bytes moved, not flops; the 3x page-bytes charge for 'gather' (copy
    write + compute re-reads on top of the pool read) is the modeling
    assumption the fused-vs-gather ratio rests on — it is an input of the
    model, not a measurement (no TPU in this container; see kernel_smoke
    for what IS measured).  ``kv_quant`` models the quantized page pool
    (kernels dequantize in registers): K/V and pooled router keys become
    1-byte codes plus one fp32 scale per token row / per page; the linear
    totals stay fp32."""
    h = HKV * N_REP
    t_n = ctx // BK
    k_sel = max(1, round(K_FRAC * t_n))
    if kv_quant == "none":
        page_bytes = batch * HKV * k_sel * BK * DH * BF16 * 2    # K + V
        pooled_bytes = batch * HKV * t_n * DH * F32              # router keys
    else:
        page_bytes = batch * HKV * k_sel * BK * (DH + F32) * 2   # codes+scale
        pooled_bytes = batch * HKV * t_n * (DH + F32)
    state_bytes = batch * HKV * (DH * DH + DH) * F32             # h_tot/z_tot
    if method == "static":
        bytes_ = batch * HKV * ctx * DH * BF16 * 2
        flops = batch * h * ctx * DH * 4
    else:
        # sparse branch QK^T + PV over the routed pages + linear correction
        flops = (batch * h * k_sel * BK * DH * 4
                 + batch * h * DH * DH * 2)
        if method == "fused":
            bytes_ = page_bytes + pooled_bytes + state_bytes
        elif method == "gather":
            bytes_ = 3 * page_bytes + pooled_bytes + state_bytes
        else:
            raise ValueError(method)
    t = max(flops / V5E_PEAKS.flops_bf16, bytes_ / V5E_PEAKS.hbm_bw)
    return LAYERS * t


def modeled_table() -> list[dict]:
    rows = []
    for ctx in CONTEXTS:
        for batch in BATCHES:
            ts = {m: modeled_step(batch, ctx, m)
                  for m in ("fused", "gather", "static")}
            t_q = modeled_step(batch, ctx, "fused", kv_quant="int8")
            rows.append({
                "ctx": ctx, "batch": batch,
                "fused_us": round(ts["fused"] * 1e6, 1),
                "fused_int8_us": round(t_q * 1e6, 1),
                "gather_us": round(ts["gather"] * 1e6, 1),
                "static_us": round(ts["static"] * 1e6, 1),
                "fused_tok_s": round(batch / ts["fused"]),
                "gather_tok_s": round(batch / ts["gather"]),
                "static_tok_s": round(batch / ts["static"]),
                "fused_vs_gather_x": round(ts["gather"] / ts["fused"], 2),
                "fused_vs_static_x": round(ts["static"] / ts["fused"], 2),
                "int8_pool_vs_bf16_x": round(ts["fused"] / t_q, 2),
            })
    return rows


# ---------------------------------------------------------------------------
# measured: interpret-mode kernel smoke (parity + wall time)
# ---------------------------------------------------------------------------

def kernel_smoke() -> dict:
    """Run the fused decode kernel (interpret) against the gather reference
    on one routed state; assert parity and record wall times."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.models import attention as A
    from repro.serve.scenario import make_paged_attention_state

    lengths = jnp.asarray([37, 16, 70], jnp.int32)
    cfg, params, cache, pt, x_t = make_paged_attention_state()
    active = jnp.ones((lengths.shape[0],), bool)
    out = {}
    for impl, quant in (("fused", "none"), ("fused", "int8"),
                        ("gather", "none")):
        c = dataclasses.replace(cfg, paged_impl=impl,
                                decode_quant_bits=quant)
        fn = jax.jit(lambda xt, ca, _c=c: A.decode_step_paged(
            params, _c, xt, ca, page_table=pt, lengths=lengths,
            active=active))
        o, _ = fn(x_t, dict(cache))
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        o, _ = fn(x_t, dict(cache))
        jax.block_until_ready(o)
        out[f"{impl}_{quant}"] = {
            "step_ms": round((time.perf_counter() - t0) * 1e3, 2),
            "out": np.asarray(o)}
    ref = out["gather_none"]["out"]
    err_fp = float(np.abs(out["fused_none"]["out"] - ref).max())
    err_q = float(np.linalg.norm(out["fused_int8"]["out"] - ref)
                  / np.linalg.norm(ref))
    assert err_fp < 5e-5, f"fused fp32 decode diverged: {err_fp}"
    assert err_q < 0.05, f"fused int8 decode outside QAT noise: {err_q}"
    return {
        "parity": {"fp32_max_abs_err": err_fp, "int8_rel_err": round(err_q, 5)},
        "interpret_step_ms": {k: v["step_ms"] for k, v in out.items()},
        "note": "interpret-mode CPU times; parity is the signal here",
    }


# ---------------------------------------------------------------------------
# measured: engine throughput (CPU proxy)
# ---------------------------------------------------------------------------

def engine_throughput(seed: int = 0) -> dict:
    """Mixed-length workload tokens/sec: paged engine (gather path — the
    XLA-compiled CPU proxy) vs static waves, across batch sizes."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models.api import build_model
    from repro.serve import (EngineConfig, ServeEngine, StaticWaveEngine,
                             make_mixed_requests)

    cfg = get_smoke_config("qwen3_14b", n_layers=4, d_model=128, d_ff=256,
                           num_heads=4, num_kv_heads=2, head_dim=32,
                           vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    work = [(12, 48), (8, 8), (150, 8), (16, 12), (10, 48), (24, 8),
            (9, 8), (14, 48), (20, 12), (11, 8), (30, 48), (13, 8)]
    out = {}
    for slots in (2, 8):
        row = {}
        for name, eng_cls, kw in (
                ("paged_gather", ServeEngine, {"paged_impl": "gather"}),
                ("static_wave", StaticWaveEngine, {})):
            eng = eng_cls(model, EngineConfig(
                max_slots=slots, max_len=256, prefill_chunk=64, **kw))
            eng.load(params)
            for r in make_mixed_requests(cfg.vocab_size, work, seed=seed):
                eng.submit(r)                       # warm-up: compile
            eng.run_to_completion(max_steps=4000)
            reqs = make_mixed_requests(cfg.vocab_size, work, seed=seed)
            for r in reqs:
                eng.submit(r)
            t0 = time.perf_counter()
            eng.run_to_completion(max_steps=4000)
            dt = time.perf_counter() - t0
            toks = sum(len(r.output or []) for r in reqs)
            row[name] = {"tok_per_s": round(toks / dt, 2),
                         "seconds": round(dt, 3)}
        row["paged_vs_static_x"] = round(
            row["paged_gather"]["tok_per_s"]
            / row["static_wave"]["tok_per_s"], 2)
        out[f"slots_{slots}"] = row
    return out


def run(smoke: bool = False) -> dict:
    rows = modeled_table()
    payload = {
        "geometry": {"layers": LAYERS, "hkv": HKV, "n_rep": N_REP, "dh": DH,
                     "page_tokens": BK, "k_frac": K_FRAC},
        "modeled_v5e": rows,
        "kernel_smoke": kernel_smoke(),
    }
    # acceptance: fused beats gather on step latency at batch >= 8, long
    # ctx, per the v5e byte model above, AND the shipped kernel actually
    # runs and agrees with the reference (kernel_smoke asserts parity) —
    # the roofline half guards the byte accounting, not a measurement
    wins = [r for r in rows if r["batch"] >= 8 and r["ctx"] >= 32768]
    payload["acceptance_fused_beats_gather_modeled"] = all(
        r["fused_vs_gather_x"] > 1.0 for r in wins)
    if not smoke:
        payload["engine_measured_cpu"] = engine_throughput()
    save_result("fig6_paged_decode", payload)
    if not smoke:
        # only full runs refresh the cross-PR trajectory artifact — smoke
        # runs skip engine_measured_cpu and would drop it from the file
        with open(TOP_LEVEL_JSON, "w") as f:
            json.dump(payload, f, indent=1)
    print(markdown_table(rows, ["ctx", "batch", "fused_us", "fused_int8_us",
                                "gather_us", "static_us",
                                "fused_vs_gather_x", "fused_vs_static_x",
                                "int8_pool_vs_bf16_x"]))
    print(f"\nkernel smoke: {payload['kernel_smoke']['parity']}")
    print(f"acceptance (fused beats gather, batch>=8 long ctx, modeled): "
          f"{payload['acceptance_fused_beats_gather_modeled']}")
    if not smoke:
        print(f"engine (CPU proxy): {payload['engine_measured_cpu']}")
    assert payload["acceptance_fused_beats_gather_modeled"]
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="modeled table + interpret-mode kernel parity only "
                         "(the CI fast-job invocation)")
    args = ap.parse_args()
    run(smoke=args.smoke)
