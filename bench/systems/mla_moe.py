"""Drives ``ServeEngine`` for an MLA + expert-layer stack (DeepSeek-V2
layout: a leading dense-FFN layer, then MLA attention over latent pages
with one chip's share of a dropless expert layer) as a decode instance.

The loop is ``systems/lm``'s closed loop with one difference: set-up
builds the caches.  After warming, the first ``clients`` requests are
admitted and the engine steps until every slot is decoding, so the
window opens in steady state.  Those requests are counted for the tokens
they get in the window (tokens served before it are not) and may be
compared.  Each step also records the engine's expert counters.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench.core import log
from bench.systems import lm

COUNTERS = ("prefill_tokens", "moe_held_rows", "moe_active_experts")


def model_config(cfg: dict):
    from repro.models.mla import MLAConfig
    from repro.models.moe import MoEConfig
    from repro.models.transformer import ModelConfig
    s = cfg["sla2"]
    n_dense = cfg["first_k_dense_replace"]
    return ModelConfig(
        name="bench_mla_moe", family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        layer_kinds=("mla_moe",), first_kinds=("mla_dense",) * n_dense,
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_dim=cfg["qk_nope_head_dim"],
                      qk_rope_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"],
                      q_lora_rank=cfg["q_lora_rank"] or 0),
        moe=MoEConfig(num_experts=cfg["router_num_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      num_shared=cfg["n_shared_experts"],
                      held_experts=cfg["n_routed_experts"],
                      expert_offset=cfg["expert_offset"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], mechanism="sla2",
        block_q=s["block_q"], block_k=s["block_k"], k_frac=s["k_frac"],
        max_target_len=cfg["max_position_embeddings"], dtype=cfg["dtype"])


class System(lm.System):
    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.models.api import build_model
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.model = build_model(model_config(cfg))
        self.rng = np.random.default_rng(seed)
        self.reqs, self.info, self.live = {}, {}, set()
        self._uid = 0
        self._ref_logits = {}

    def warm(self, traffic):
        """Compile both programs (``lm.System.warm``), then fill every
        slot: admit ``clients`` requests and step until none is
        prefilling."""
        super().warm(traffic)
        eng = self.eng
        while len(self.live) < self.mix["clients"]:
            self._submit(traffic, 0.0, 0.0)
        n = 0
        while eng._queue or eng._prefill_order:
            eng.step()
            n += 1
        jax.block_until_ready(eng.caches)
        self._note_slots()
        for uid in self.live:
            inf = self.info[uid]
            inf["out0"] = len(self.reqs[uid].output)
            inf["prefilled"] = inf["prompt"]
        log(f"set-up: {n} steps filled {len(eng._slots)} slots")

    def _observe(self, t, step):
        """``lm.System._observe`` for requests that may hold tokens from
        before the window (``out0``): only the window's are timed."""
        self._note_slots()
        rows, done = [], []
        for uid in sorted(self.live):
            req, inf = self.reqs[uid], self.info[uid]
            base = inf.get("out0", 0)
            for m in range(base + len(inf["tokens"]), len(req.output)):
                inf["tokens"].append(t)
                if m >= 1:                  # token m came from decode
                    rows.append(inf["prompt"] + m)
            if req.t_finish is not None:
                done.append(uid)
        for uid in done:
            self.live.discard(uid)
        step["decode_rows"] = rows
        n = step["prefill_tokens"]
        step["prefill"] = None
        if n:
            for uid in sorted(self.info):
                inf = self.info[uid]
                if inf["prefilled"] < inf["prompt"]:
                    step["prefill"] = (inf["prefilled"], n)
                    inf["prefilled"] += n
                    break

    def run_window(self, seconds, traffic, spans, tracer, record):
        """Closed loop: a finished request is replaced at once."""
        eng, mix = self.eng, self.mix
        window_uids = set(self.live)
        tracer.start()
        t_start = time.perf_counter()
        stats0 = dict(eng.stats)
        while True:
            now = time.perf_counter() - t_start
            with spans("bench.submit"):
                while len(self.live) < mix["clients"]:
                    window_uids.add(self._uid)
                    self._submit(traffic, now, now)
            before = dict(eng.stats)
            with spans("bench.step") as sp:
                occupied = eng.step()
            t1 = time.perf_counter() - t_start
            step = {"t0": sp.t0 - t_start, "t1": t1, "occupied": occupied}
            for k in COUNTERS:
                step[k] = eng.stats[k] - before[k]
            self._observe(t1, step)
            record.steps.append(step)
            if tracer.due(t1):
                with spans("bench.sync"):
                    jax.block_until_ready(eng.caches)
                tracer.stop(time.perf_counter() - t_start, len(record.steps))
            if t1 >= seconds:
                with spans("bench.sync"):
                    jax.block_until_ready(eng.caches)
                record.window_s = time.perf_counter() - t_start
                record.counters = {k: eng.stats[k] - stats0[k]
                                   for k in stats0}
                break
        record.extra["max_slots"] = eng.cfg.max_slots
        record.extra["k_sel"] = lm.decode_k_sel(self.cfg, self.eng_max_len())
        record.extra["spans"] = [(n, a - t_start, b - t_start)
                                 for n, a, b in spans.items]
        record.requests = [self.info[u] for u in sorted(window_uids)]
        record.extra["attempted"] = len(record.requests)
        record.extra["failed"] = 0          # a closed loop only stops clients
        record.extra["preemptions"] = record.counters.get("preemptions", 0)
        record.extra["pool_peak_pages"] = eng.stats["pool_peak_pages"]
        record.extra["num_pages"] = eng.allocator.num_pages
        log(f"pool: peak {eng.stats['pool_peak_pages']} of "
            f"{eng.allocator.num_pages - 1} pages; "
            f"{record.extra['preemptions']} preemptions in the window")

    # -- comparison ------------------------------------------------------
    def _note_slots(self):
        """The slot each live request first ran in (for ``sample``)."""
        for slot, st in self.eng._slots.items():
            self.info[st.req.uid].setdefault("slot", slot)

    def sample(self):
        """Requests to compare, finished or not: the one with the longest
        context, then seeded draws of requests from other slots until
        ``check['requests']`` slots are in (or every slot that served is)."""
        served = [u for u in sorted(self.reqs) if self.reqs[u].output]
        if not served:
            return []
        size = lambda u: len(self.reqs[u].prompt) + len(self.reqs[u].output)
        longest = max(served, key=size)
        pick = [longest]
        slots = {self.info[longest].get("slot")}
        rest = [u for u in served if u != longest]
        rng = np.random.default_rng(self.seed ^ 0x5EED)
        for i in rng.permutation(len(rest)):
            if len(pick) >= self.mix["check"]["requests"]:
                break
            slot = self.info[rest[i]].get("slot")
            if slot not in slots:
                pick.append(rest[i])
                slots.add(slot)
        return sorted(pick)

    def gaps(self, precision: str = "fp32") -> dict:
        """Per sampled request, by how much each served token's float32
        reference logit lies below the reference's best (0 where the
        token is the reference's first).  With ``precision`` 'fp8' (the
        control) or 'bf16' (the witness) the token that reference puts
        first stands in for the served one."""
        from bench.reference import mla_moe as ref
        out = {}
        max_len = self.eng_max_len()
        for uid in self.sample():
            req = self.reqs[uid]
            served = np.asarray(req.output, np.int64)
            seq = np.concatenate([req.prompt, served[:-1]]).astype(np.int32)
            if uid not in self._ref_logits:
                self._ref_logits[uid] = ref.logits(
                    self.params, self.cfg, seq, len(req.prompt), max_len,
                    "fp32")
            logits = self._ref_logits[uid]
            if precision != "fp32":
                served = ref.logits(self.params, self.cfg, seq,
                                    len(req.prompt), max_len,
                                    precision).argmax(-1)
            gap = logits.max(-1) - logits[np.arange(len(served)), served]
            log(f"{precision} request {uid} (slot "
                f"{self.info[uid].get('slot')}): prompt {len(req.prompt)} "
                f"served {len(served)} widest gap {gap.max():.6g} at token "
                f"{int(gap.argmax())}; {int((gap > 0).sum())} tokens not "
                f"the reference's first")
            out[uid] = gap
        return out

    def compare(self, precision: str = "fp32") -> dict:
        """Over the sampled requests' served tokens: the widest gap
        (``logit_gap``, as ``lm.System.compare``), the share of tokens
        that are not the float32 reference's first
        (``off_argmax_share``) and the mean gap (``mean_gap``), against
        the MLA + expert reference; ``precision='fp8'`` is the control."""
        per = self.gaps(precision)
        if not per:
            return dict.fromkeys(("logit_gap", "off_argmax_share",
                                  "mean_gap"), float("inf"))
        g = np.concatenate(list(per.values()))
        return {"logit_gap": float(g.max()),
                "off_argmax_share": float((g > 0).mean()),
                "mean_gap": float(g.mean()), "compared_tokens": len(g),
                "compared_requests": len(per)}


def model_flops(record, cfg: dict) -> float:
    from bench.costs import model_mla_moe
    return sum(model_mla_moe.step_flops(cfg, s["prefill"], s["decode_rows"],
                                        record.extra["k_sel"],
                                        s["moe_held_rows"])
               for s in record.steps)
