"""Figure 5 reproduction: end-to-end generation latency vs sparsity.

Latency model on one TPU v5e chip (same roofline pieces as fig4):
    T_e2e = steps x (T_attention(s) + T_rest)
T_rest (FFN/projections/norms) comes from the DiT geometry and does NOT
shrink with attention sparsity — exactly the paper's Amdahl story: a 13.9x
attention speedup becomes ~2.3x end-to-end on Wan-1.3B (Fig. 5a) and more
on Wan-14B where attention dominates (4.35x, Fig. 5b).

A second, *measured* section serves a mixed-length LM workload through the
continuous-batching ServeEngine (paged KV, chunked prefill) and the legacy
StaticWaveEngine, reporting wall-clock tokens/sec for both: the long prompt
in the mix stalls each static wave, while the paged engine interleaves its
prefill chunks with ongoing decode.
"""
from __future__ import annotations

import time

from benchmarks.common import markdown_table, save_result
from benchmarks.fig4_kernel_speed import modeled_time
from repro.launch.mesh import V5E, chip_peaks

V5E_PEAKS = chip_peaks(V5E)     # the chip these rooflines model

MODELS = {
    # name: (N tokens, d_model, heads, head_dim, d_ff, layers, steps)
    "wan_1.3b_480p": (32768, 1536, 12, 128, 8960, 30, 50),
    "wan_14b_720p": (75600, 5120, 40, 128, 13824, 40, 50),
}


def rest_time(n, d_model, d_ff, layers) -> float:
    """Non-attention per-step time: qkvo projections + FFN (gelu, ungated
    uses 2 mats; Wan uses ~3x d_ff) + norms, roofline max per op."""
    flops = layers * n * (2 * 4 * d_model * d_model      # qkvo
                          + 2 * 2 * d_model * d_ff       # ffn
                          + 2 * 4 * d_model * d_model)   # cross-attn proj
    bytes_ = layers * n * d_model * 2 * 12
    return max(flops / V5E_PEAKS.flops_bf16, bytes_ / V5E_PEAKS.hbm_bw)


def serve_throughput(arch: str = "qwen3_14b", seed: int = 0) -> dict:
    """Measured tokens/sec: continuous paged engine vs static waves on a
    mixed-length workload (CPU, smoke-scale model; the ratio, not the
    absolute rate, is the result)."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models.api import build_model
    from repro.serve import (EngineConfig, ServeEngine, StaticWaveEngine,
                             make_mixed_requests)

    # big enough that per-step compute dominates dispatch overhead
    cfg = get_smoke_config(arch, n_layers=4, d_model=128, d_ff=256,
                           num_heads=4, num_kv_heads=2, head_dim=32,
                           vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    ecfg = EngineConfig(max_slots=4, max_len=256, prefill_chunk=64)
    # mixed lengths on BOTH ends: one long prompt, and decode budgets from 8
    # to 64 tokens.  A static wave drains at its slowest member, idling the
    # other slots; the paged engine refills them mid-flight.
    work = [(12, 64), (8, 8), (150, 8), (16, 12), (10, 64), (24, 8),
            (9, 8), (14, 64), (20, 12), (11, 8), (30, 64), (13, 8),
            (18, 12), (22, 64), (15, 8), (26, 16)]
    requests = lambda: make_mixed_requests(cfg.vocab_size, work, seed=seed)

    out = {}
    for name, eng_cls in (("continuous_paged", ServeEngine),
                          ("static_wave", StaticWaveEngine)):
        eng = eng_cls(model, ecfg)
        eng.load(params)
        warm = requests()            # warm-up: compile every step-fn shape
        for r in warm:
            eng.submit(r)
        eng.run_to_completion(max_steps=4000)
        reqs = requests()
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run_to_completion(max_steps=4000)
        dt = time.perf_counter() - t0
        toks = sum(len(r.output or []) for r in reqs)
        out[name] = {"tokens": toks, "seconds": round(dt, 3),
                     "tok_per_s": round(toks / dt, 2)}
    out["speedup_x"] = round(out["continuous_paged"]["tok_per_s"]
                             / out["static_wave"]["tok_per_s"], 2)
    return out


def run(measure_serving: bool = True) -> dict:
    rows = []
    summary = {}
    for name, (n, dm, h, dh, dff, layers, steps) in MODELS.items():
        t_rest = rest_time(n, dm, dff, layers)
        t_attn_full = layers * h * modeled_time(n, dh, sparsity=None,
                                                quant=False, linear=False)
        t_full = steps * (t_attn_full + t_rest)
        rows.append({"model": name, "method": "FullAttention",
                     "attn_s/step": round(t_attn_full, 3),
                     "e2e_s": round(t_full, 1), "speedup_x": 1.0})
        for s in (0.90, 0.95, 0.97):
            t_attn = layers * h * modeled_time(n, dh, sparsity=s,
                                               quant=True, linear=True)
            t = steps * (t_attn + t_rest)
            rows.append({"model": name, "method": f"SLA2 {100 * s:.0f}%",
                         "attn_s/step": round(t_attn, 3),
                         "e2e_s": round(t, 1),
                         "speedup_x": round(t_full / t, 2)})
        summary[name] = {
            "attn_speedup_97": round(t_attn_full / t_attn, 1),
            "e2e_speedup_97": rows[-1]["speedup_x"]}
    payload = {"rows": rows, "summary": summary,
               "paper": {"wan_1.3b_480p": {"e2e": 2.30},
                         "wan_14b_720p": {"e2e": 4.35}}}
    if measure_serving:
        payload["serving_mixed_length"] = serve_throughput()
    save_result("fig5_e2e_latency", payload)
    print(markdown_table(rows, ["model", "method", "attn_s/step", "e2e_s",
                                "speedup_x"]))
    print(f"\nsummary: {summary} (paper e2e: 2.30x / 4.35x)")
    if measure_serving:
        sv = payload["serving_mixed_length"]
        print(f"serving (mixed-length, measured): continuous "
              f"{sv['continuous_paged']['tok_per_s']} tok/s vs static wave "
              f"{sv['static_wave']['tok_per_s']} tok/s "
              f"=> {sv['speedup_x']}x")
    return payload


if __name__ == "__main__":
    run()
