"""Drives ``DiffusionEngine`` (video DiT denoising) for one cell.

Closed loop: ``clients`` jobs are in flight (the engine's slots plus a
waiting queue); a finished job is replaced at once.  A job is a fixed
number of Euler steps over seeded noise latents and a seeded text
embedding.  Throughput is counted in request-steps: one denoise step of
one job.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.core import log


def model_config(cfg: dict):
    from repro.models.dit import DiTConfig
    s = cfg["sla2"]
    return DiTConfig(
        name="bench_" + cfg.get("name", "dit"), n_layers=cfg["num_layers"],
        d_model=cfg["dim"], num_heads=cfg["num_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["ffn_dim"], c_latent=cfg["in_dim"],
        n_text=cfg["text_len"], mechanism="sla2", block_q=s["block_q"],
        block_k=s["block_k"], k_frac=s["k_frac"], quant_bits=s["quant_bits"],
        t_emb_dim=cfg["freq_dim"], dtype=cfg["dtype"],
        max_target_len=cfg["latent_tokens"])


class System:
    """One DiT cell: weights from the seed, the engine, the window and
    the comparison with the plain reference."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.models.api import build_model
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.model = build_model(model_config(cfg))
        self.rng = np.random.default_rng(seed)
        self.jobs = {}                  # uid -> (latents, text, steps)
        self.finished = []
        self.slot_of = {}               # uid -> the engine slot it ran in
        self._uid = 0

    # -- set-up ----------------------------------------------------------
    def load(self):
        from repro.serve.diffusion import (DiffusionEngine,
                                           DiffusionEngineConfig)
        self.params = weights.make(self.model.init, self.seed)
        e = self.mix["engine"]
        self.ecfg = DiffusionEngineConfig(
            max_slots=e["max_slots"], n_latent=self.cfg["latent_tokens"],
            max_steps=self.cfg["engine"]["max_steps"])
        self.eng = DiffusionEngine(self.model, self.params, self.ecfg)

    def new_job(self, traffic):
        from repro.serve.diffusion import VideoRequest
        i = traffic.next_index()
        steps = traffic.size(i)["steps"]
        lat = self.rng.standard_normal(
            (self.cfg["latent_tokens"], self.cfg["in_dim"])).astype(np.float32)
        # the engine casts text to bf16; draw it there so both sides agree
        text = np.asarray(jnp.asarray(self.rng.standard_normal(
            (self.cfg["text_len"], self.cfg["dim"])), jnp.bfloat16)
            .astype(jnp.float32))
        uid = self._uid
        self._uid += 1
        self.jobs[uid] = (lat, text, steps)
        return VideoRequest(uid=uid, latents=lat, text=text, n_steps=steps)

    def warm(self, traffic):
        """Fill the loop and run one engine step: it admits a job into
        every slot, so every program the window runs is compiled."""
        for _ in range(self.mix["clients"]):
            self.eng.submit(self.new_job(traffic))
        self.eng.step()
        self._note_slots()
        jax.block_until_ready(self.eng._latents)

    def _note_slots(self):
        for slot, req in self.eng.scheduler.active.items():
            self.slot_of[req.uid] = slot

    def in_flight(self) -> int:
        return self._uid - len(self.finished)

    # -- window ----------------------------------------------------------
    def run_window(self, seconds, traffic, spans, tracer, record):
        eng = self.eng
        tracer.start()
        t_start = time.perf_counter()
        stats0 = dict(eng.stats)
        while True:
            before = dict(eng.stats)
            with spans("bench.step") as sp:
                done = eng.step()
            t1 = time.perf_counter()
            self._note_slots()
            self.finished.extend(done)
            with spans("bench.submit"):
                while self.in_flight() < self.mix["clients"]:
                    eng.submit(self.new_job(traffic))
            record.steps.append({
                "t0": sp.t0 - t_start, "t1": t1 - t_start,
                "occupancy": eng.stats["occupancy_sum"]
                - before["occupancy_sum"],
                "request_steps": eng.stats["denoise_steps"]
                - before["denoise_steps"]})
            if tracer.due(t1 - t_start):
                with spans("bench.sync"):
                    jax.block_until_ready(eng._latents)
                tracer.stop(time.perf_counter() - t_start, len(record.steps))
            if t1 - t_start >= seconds:
                break
        with spans("bench.sync"):
            jax.block_until_ready(eng._latents)
        record.window_s = time.perf_counter() - t_start
        record.counters = {k: eng.stats[k] - stats0[k] for k in stats0}
        record.extra["max_slots"] = self.ecfg.max_slots
        record.extra["spans"] = [(n, a - t_start, b - t_start)
                                 for n, a, b in spans.items]
        record.extra["finished"] = len(self.finished)
        record.requests = [{"uid": r.uid} for r in self.finished]
        record.extra["attempted"] = self._uid
        record.extra["failed"] = 0

    def memory_peak(self) -> int:
        return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])

    def free(self):
        del self.eng

    # -- comparison ------------------------------------------------------
    def sample(self):
        """The finished jobs whose latents are compared: ``per_slot``
        seeded draws from the jobs each engine slot finished, so every
        slot's rows are checked."""
        rng = np.random.default_rng(self.seed ^ 0x5EED)
        by_slot = {}
        for r in self.finished:
            by_slot.setdefault(self.slot_of.get(r.uid), []).append(r)
        pick = []
        for slot in sorted(by_slot, key=str):
            jobs = by_slot[slot]
            n = min(self.mix["check"]["per_slot"], len(jobs))
            pick += [jobs[i] for i in rng.choice(len(jobs), n, replace=False)]
        return sorted(pick, key=lambda r: r.uid)

    def compare(self, precision: str = "fp32") -> dict:
        """Displacement error of each sampled job's final latents against
        the reference: |out - ref| / |ref - noise|.  With
        ``precision='fp8'`` the reference itself (in fp8) stands in for
        the program's output: the control."""
        from bench.reference import dit as ref
        worst = 0.0
        jobs = self.sample()
        if not jobs:
            return {"displacement_rel_l2": float("inf")}
        for r in jobs:
            lat, text, steps = self.jobs[r.uid]
            want = ref.denoise(self.params, self.cfg, lat, text, steps, "fp32")
            got = (ref.denoise(self.params, self.cfg, lat, text, steps, "fp8")
                   if precision == "fp8" else r.output)
            err = float(np.linalg.norm(np.asarray(got, np.float64) - want)
                        / max(np.linalg.norm(want - lat), 1e-30))
            log(f"job {r.uid} (slot {self.slot_of.get(r.uid)}): "
                f"displacement rel L2 {err:.6g}")
            worst = max(worst, err)
        return {"displacement_rel_l2": worst,
                "compared_slots": len({self.slot_of.get(r.uid) for r in jobs})}


def model_flops(record, cfg: dict) -> float:
    """Model FLOPs of the request-steps in ``record.steps``."""
    from bench.costs import model_dit
    per = model_dit.flops_per_request_step(cfg)
    return per * sum(s["request_steps"] for s in record.steps)
