"""Drives ``ServeEngine`` (paged continuous batching of a decoder-only LM)
for one cell.

Open loop: requests are due on a seeded Poisson schedule and are
submitted when due, whatever the engine is doing; each is timed from its
due time.  Closed loop: ``clients`` requests are in flight and a finished
one is replaced at once.  Decoding is greedy.  Tokens are timestamped on
the host when ``len(request.output)`` grows after an engine step (the
engine samples on the host, so a token exists once ``step()`` returns).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import weights
from bench.core import log
from bench.traffic.gen import token_ids


def model_config(cfg: dict):
    from repro.models.transformer import ModelConfig
    s = cfg["sla2"]
    return ModelConfig(
        name="bench_lm", family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], mechanism="sla2",
        block_q=s["block_q"], block_k=s["block_k"], k_frac=s["k_frac"],
        max_target_len=cfg["max_position_embeddings"], dtype=cfg["dtype"])


class System:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.models.api import build_model
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.model = build_model(model_config(cfg))
        self.rng = np.random.default_rng(seed)
        self.reqs = {}                  # uid -> Request
        self.info = {}                  # uid -> timing dict
        self.live = set()               # submitted, not finished
        self._uid = 0

    # -- set-up ----------------------------------------------------------
    def load(self):
        from repro.serve import EngineConfig, ServeEngine
        self.params = weights.make(self.model.init, self.seed)
        e = dict(self.mix["engine"])
        e.setdefault("prefill_chunk", self.cfg["engine"]["prefill_chunk"])
        self.eng = ServeEngine(self.model, EngineConfig(**e))
        self.eng.load(self.params)

    def warm(self, traffic):
        """Serve one short request to the end: compiles the prefill chunk
        and the batched decode step, the only programs the window runs."""
        from repro.serve import Request
        chunk = self.eng.chunk
        self.eng.submit(Request(uid=-1, prompt=token_ids(
            self.rng, chunk + 1, self.cfg["vocab_size"]), max_new_tokens=2))
        self.eng.run_to_completion()
        self.eng.completed = []
        jax.block_until_ready(self.eng.caches)

    def _submit(self, traffic, due, t_now):
        from repro.serve import Request
        i = traffic.next_index()
        size = traffic.size(i)
        uid = self._uid
        self._uid += 1
        req = Request(uid=uid, prompt=token_ids(self.rng, size["prompt"],
                                                self.cfg["vocab_size"]),
                      max_new_tokens=size["output"])
        self.eng.submit(req)
        self.reqs[uid] = req
        self.info[uid] = {"uid": uid, "due": due, "submit": t_now,
                          "prompt": size["prompt"], "output": size["output"],
                          "tokens": [], "prefilled": 0}
        self.live.add(uid)

    def _observe(self, t, step):
        """After a step: token times, finished requests and what the step
        served (decode rows with their context lengths, the prefill
        chunk)."""
        rows, done = [], []
        for uid in sorted(self.live):
            req, inf = self.reqs[uid], self.info[uid]
            n_old, n_new = len(inf["tokens"]), len(req.output)
            for m in range(n_old, n_new):
                inf["tokens"].append(t)
                if m >= 1:                  # token m came from decode
                    rows.append(inf["prompt"] + m)
            if req.t_finish is not None:
                done.append(uid)
        for uid in done:
            self.live.discard(uid)
        step["decode_rows"] = rows
        # FCFS chunked prefill: the chunk went to the oldest unfinished
        # prompt, which the harness tracks from the engine's counter
        n = step["prefill_tokens"]
        step["prefill"] = None
        if n:
            for uid in sorted(self.info):
                inf = self.info[uid]
                if inf["prefilled"] < inf["prompt"]:
                    step["prefill"] = (inf["prefilled"], n)
                    inf["prefilled"] += n
                    break

    # -- window ----------------------------------------------------------
    def run_window(self, seconds, traffic, spans, tracer, record):
        eng, mix = self.eng, self.mix
        open_loop = mix["loop"] == "open"
        due = (traffic.due_times(seconds + mix.get("drain_seconds", 0))
               if open_loop else [])
        nxt = 0
        tracer.start()
        t_start = time.perf_counter()
        stats0 = dict(eng.stats)
        window_uids = set()
        draining = False
        while True:
            now = time.perf_counter() - t_start
            with spans("bench.submit"):
                if open_loop:
                    while nxt < len(due) and due[nxt] <= now:
                        if due[nxt] <= seconds:
                            window_uids.add(self._uid)
                        self._submit(traffic, due[nxt], now)
                        nxt += 1
                elif not draining:
                    while len(self.live) < mix["clients"]:
                        window_uids.add(self._uid)
                        self._submit(traffic, now, now)
            if not self.live:
                if nxt >= len(due) or (draining and self._window_done(
                        window_uids)):
                    break
                time.sleep(max(0.0, min(due[nxt] - now, 0.01)))
                continue
            before = dict(eng.stats)
            with spans("bench.step") as sp:
                occupied = eng.step()
            t1 = time.perf_counter() - t_start
            step = {"t0": sp.t0 - t_start, "t1": t1, "occupied": occupied,
                    "prefill_tokens": eng.stats["prefill_tokens"]
                    - before["prefill_tokens"],
                    "drain": draining}
            self._observe(t1, step)
            record.steps.append(step)
            if tracer.due(t1):
                with spans("bench.sync"):
                    jax.block_until_ready(eng.caches)
                tracer.stop(time.perf_counter() - t_start, len(record.steps))
            if not draining and t1 >= seconds:
                with spans("bench.sync"):
                    jax.block_until_ready(eng.caches)
                record.window_s = time.perf_counter() - t_start
                record.counters = {k: eng.stats[k] - stats0[k]
                                   for k in stats0}
                draining = open_loop
                if not draining:
                    break
            if draining and (self._window_done(window_uids) or t1 > seconds
                             + mix.get("drain_seconds", 0)):
                break
        record.extra["max_slots"] = eng.cfg.max_slots
        record.extra["k_sel"] = decode_k_sel(self.cfg, self.eng_max_len())
        record.extra["spans"] = [(n, a - t_start, b - t_start)
                                 for n, a, b in spans.items]
        record.requests = [self.info[u] for u in sorted(window_uids)]
        record.extra["attempted"] = len(record.requests)
        # open loop: a request due in the window that never answered by
        # the end of the drain failed; a closed loop only stops clients
        record.extra["failed"] = sum(1 for r in record.requests
                                     if not r["tokens"]) if open_loop else 0
        record.extra["preemptions"] = record.counters.get("preemptions", 0)
        # pages held at once (the engine's low-water mark of its free list)
        record.extra["pool_peak_pages"] = eng.stats["pool_peak_pages"]
        record.extra["num_pages"] = eng.allocator.num_pages
        log(f"pool: peak {eng.stats['pool_peak_pages']} of "
            f"{eng.allocator.num_pages - 1} pages; "
            f"{record.extra['preemptions']} preemptions in the window")

    def _window_done(self, uids) -> bool:
        return all(self.info[u]["tokens"] for u in uids)

    def memory_peak(self) -> int:
        return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])

    def free(self):
        del self.eng

    # -- comparison ------------------------------------------------------
    def sample(self):
        """Finished requests to compare: the longest, then seeded draws of
        the others until they hold ``min_tokens`` served tokens (or every
        finished request is in)."""
        done = [u for u in sorted(self.reqs)
                if self.reqs[u].t_finish is not None]
        if not done:
            return []
        longest = max(done, key=lambda u: len(self.reqs[u].prompt)
                      + len(self.reqs[u].output))
        pick = [longest]
        n_tok = len(self.reqs[longest].output)
        rest = [u for u in done if u != longest]
        rng = np.random.default_rng(self.seed ^ 0x5EED)
        for i in rng.permutation(len(rest)):
            if n_tok >= self.mix["check"]["min_tokens"]:
                break
            pick.append(rest[i])
            n_tok += len(self.reqs[rest[i]].output)
        return sorted(pick)

    def compare(self, precision: str = "fp32") -> dict:
        """Widest gap by which a served token's reference logit lies below
        the reference's best, over the sampled requests.  With
        ``precision='fp8'`` the token the fp8 reference puts first stands
        in for the served one: the control."""
        from bench.reference import lm as ref
        worst, n_tok = 0.0, 0
        uids = self.sample()
        if not uids:
            return {"logit_gap": float("inf")}
        max_len = self.eng_max_len()
        for uid in uids:
            req = self.reqs[uid]
            served = np.asarray(req.output, np.int64)
            seq = np.concatenate([req.prompt, served[:-1]]).astype(np.int32)
            logits = ref.logits(self.params, self.cfg, seq, len(req.prompt),
                                max_len, "fp32")
            if precision == "fp8":
                served = ref.logits(self.params, self.cfg, seq,
                                    len(req.prompt), max_len,
                                    "fp8").argmax(-1)
            best = logits.max(-1)
            gap = best - logits[np.arange(len(served)), served]
            log(f"request {uid}: prompt {len(req.prompt)} served "
                f"{len(served)} widest gap {gap.max():.6g} at token "
                f"{int(gap.argmax())}; {int((gap > 0).sum())} tokens not "
                f"the reference's first")
            worst = max(worst, float(gap.max()))
            n_tok += len(served)
        return {"logit_gap": worst, "compared_tokens": n_tok}

    def eng_max_len(self) -> int:
        page = self.cfg["sla2"]["block_k"]
        return -(-self.mix["engine"]["max_len"] // page) * page


def decode_k_sel(cfg: dict, max_len: int) -> int:
    """Key blocks a decode query keeps: k_frac of the slot's capacity."""
    return max(1, round(cfg["sla2"]["k_frac"] * (max_len
                                                  // cfg["sla2"]["block_k"])))


def model_flops(record, cfg: dict) -> float:
    from bench.costs import model_lm
    return sum(model_lm.step_flops(cfg, s["prefill"], s["decode_rows"],
                                   record.extra["k_sel"])
               for s in record.steps)
