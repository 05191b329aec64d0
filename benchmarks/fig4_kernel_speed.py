"""Figure 4 reproduction: kernel speed vs sparsity.

Two views (no TPU in this container, so wall-clock TOPS is out):

  (1) the ROOFLINE-MODEL speedup on TPU v5e: attention kernel time modelled
      as max(compute, HBM) per branch; SLA2's sparse branch scales with
      (1-s) and runs INT8 (2x MXU rate), the linear branch adds a fixed
      O(N d^2) term, the router O((N/b)^2 d).  Reported as the effective
      "C/t" TOPS of the paper with C = 4 N^2 d.

  (2) a measured CPU-proxy: wall time of the jnp gather implementation vs
      dense attention at small N — confirms the (1-s) compute scaling trend
      on real executions (absolute numbers are CPU-meaningless).

Paper claims at N~32k: 18.6x over FlashAttn2 at 97%; ~1.3x extra from
low-bit attention.
"""
from __future__ import annotations

import dataclasses as dc
import functools

import jax
import jax.numpy as jnp

from benchmarks.common import markdown_table, save_result, timed
from repro.core import sla2 as sla2lib
from repro.core.attention import full_attention
from repro.core.router import RouterConfig
from repro.core.sla2 import SLA2Config
from repro.launch.mesh import V5E, chip_peaks

V5E_PEAKS = chip_peaks(V5E)     # the chip these rooflines model

N_MODEL, D = 32768, 128
BQ, BK = 128, 64


def modeled_time(n: int, d: int, *, sparsity: float | None, quant: bool,
                 linear: bool) -> float:
    """Roofline time (s) of one attention head forward on one v5e chip."""
    def t_of(flops, bytes_, peak):
        return max(flops / peak, bytes_ / V5E_PEAKS.hbm_bw)

    if sparsity is None:  # dense FlashAttention
        flops = 4.0 * n * n * d
        bytes_ = 3 * n * d * 2 + n * d * 2         # q,k,v in + o out (bf16)
        return t_of(flops, bytes_, V5E_PEAKS.flops_bf16)
    keep = 1.0 - sparsity
    peak = V5E_PEAKS.flops_int8 if quant else V5E_PEAKS.flops_bf16
    t = t_of(keep * 4.0 * n * n * d,
             (2 + keep) * n * d * 2 + n * d * 2, peak)  # kv tiles ~ keep
    # router: pooled scores + topk
    t += t_of(2.0 * (n / BQ) * (n / BK) * d, 2 * (n / BQ + n / BK) * d * 4,
              V5E_PEAKS.flops_bf16)
    if linear:
        t += t_of(6.0 * n * d * d, 4 * n * d * 2, V5E_PEAKS.flops_bf16)
    return t


def run() -> dict:
    c_theory = 4.0 * N_MODEL * N_MODEL * D
    t_full = modeled_time(N_MODEL, D, sparsity=None, quant=False,
                          linear=False)
    rows = [{"kernel": "FlashAttn2 (bf16 dense)", "sparsity": "0%",
             "model_TOPS": round(c_theory / t_full / 1e12, 1),
             "speedup_x": 1.0}]
    for label, quant, linear, ss in [
            ("VSA/VMoBA-like (bf16 sparse)", False, False, (0.90, 0.95)),
            ("SLA (bf16 sparse+linear)", False, True, (0.90, 0.95)),
            ("SLA2 (int8 sparse+linear)", True, True, (0.90, 0.95, 0.97))]:
        for s in ss:
            t = modeled_time(N_MODEL, D, sparsity=s, quant=quant,
                             linear=linear)
            rows.append({"kernel": label, "sparsity": f"{100 * s:.0f}%",
                         "model_TOPS": round(c_theory / t / 1e12, 1),
                         "speedup_x": round(t_full / t, 1)})
    sla2_97 = rows[-1]["speedup_x"]
    noq_97 = t_full / modeled_time(N_MODEL, D, sparsity=0.97, quant=False,
                                   linear=True)
    quant_gain = round(sla2_97 / noq_97, 2)

    # --- CPU-proxy measured trend (small N) ---
    n_cpu, h = 2048, 2
    q, k, v = [jax.random.normal(jax.random.PRNGKey(i), (1, h, n_cpu, 64))
               for i in range(3)]
    meas = []
    t_dense, _ = timed(jax.jit(functools.partial(full_attention,
                                                 causal=False)), q, k, v)
    for s in (0.90, 0.95, 0.97):
        rc = RouterConfig(block_q=64, block_k=32, k_frac=1 - s,
                          causal=False)
        cfg = SLA2Config(router=rc, quant_bits="none", impl="gather")
        p = sla2lib.init_sla2_params(jax.random.PRNGKey(0), head_dim=64,
                                     num_heads=h, n_q_blocks=n_cpu // 64,
                                     cfg=cfg)
        fn = jax.jit(lambda q, k, v, _p=p, _c=cfg:
                     sla2lib.sla2_attention(_p, q, k, v, _c))
        t_s, _ = timed(fn, q, k, v)
        meas.append({"sparsity": f"{100 * s:.0f}%",
                     "cpu_speedup_x": round(t_dense / t_s, 2)})

    payload = {"modeled": rows, "modeled_97_speedup": sla2_97,
               "paper_97_speedup": 18.6,
               "quant_kernel_gain": quant_gain,
               "paper_quant_gain": 1.3,
               "cpu_proxy": meas}
    save_result("fig4_kernel_speed", payload)
    print(markdown_table(rows, ["kernel", "sparsity", "model_TOPS",
                                "speedup_x"]))
    print(f"\nmodeled SLA2@97% speedup {sla2_97}x (paper: 18.6x); "
          f"int8 gain {quant_gain}x (paper ~1.3x)")
    print(markdown_table(meas, ["sparsity", "cpu_speedup_x"]))
    return payload


if __name__ == "__main__":
    run()
