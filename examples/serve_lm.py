"""Serve a small SLA2 LM with mixed-length continuous batching.

    PYTHONPATH=src python examples/serve_lm.py

Trains a tiny model briefly (so generations aren't pure noise), then serves
a mixed-length workload through the continuous-batching ServeEngine
(block-paged KV cache, per-slot offsets, chunked prefill): once with plain
decode and once with self-speculative decoding, whose greedy outputs must
be token-identical.  The long prompt in the mix is prefilled in chunks
that interleave with ongoing decode.
"""
import tempfile
import time

from repro.configs import get_smoke_config
from repro.data import make_dataset
from repro.models.api import build_model
from repro.optim import AdamWConfig
from repro.serve import EngineConfig, ServeEngine, make_mixed_requests
from repro.train import TrainConfig, Trainer, TrainerConfig

# mixed lengths on both ends: mostly short prompts plus one long one, and
# decode budgets from 8 to 48 tokens
WORK = [(12, 48), (8, 8), (150, 8), (16, 48), (10, 8), (24, 32),
        (9, 48), (14, 8)]


def make_requests(cfg, seed=0):
    return make_mixed_requests(cfg.vocab_size, WORK, seed=seed)


def drive(eng, reqs):
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_to_completion(max_steps=2000)
    dt = time.perf_counter() - t0
    toks = sum(len(r.output or []) for r in reqs)
    return toks, dt, eng.stats["engine_steps"]


def main():
    cfg = get_smoke_config("h2o_danube_1_8b")   # SWA x SLA2 variant
    model = build_model(cfg)
    ds = make_dataset(cfg, seq_len=128, global_batch=8, seed=0)
    print("== brief fine-tune so the LM has structure ==")
    out = Trainer(model, TrainerConfig(
        train=TrainConfig(optimizer=AdamWConfig(lr=2e-3), warmup_steps=5,
                          total_steps=60),
        ckpt_dir=tempfile.mkdtemp(), max_steps=60, ckpt_every=60,
        log_every=20), ds).run()
    params = out["state"]["params"]

    print("\n== continuous batching (paged KV, per-slot offsets) ==")
    # the default 'auto' paged path decodes through the fused Pallas
    # page-table kernel on TPU and the jnp gather reference on CPU; pass
    # paged_impl='fused'/'gather' in EngineConfig to force either
    ecfg = EngineConfig(max_slots=4, max_len=256, prefill_chunk=32)
    eng = ServeEngine(model, ecfg)
    from repro.models.attention import resolve_paged_impl
    print("paged attention path: "
          f"{resolve_paged_impl(eng.model.cfg.attention_config())}")
    eng.load(params)
    reqs = make_requests(cfg)
    toks, dt, steps = drive(eng, reqs)
    for r in reqs:
        print(f"req {r.uid}: prompt {len(r.prompt):3d} -> "
              f"{(r.output or [])[:8]}")
    print(f"{toks} tokens in {steps} engine steps, {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, {eng.allocator.available} pages free)")

    print("\n== self-speculative decoding (linear-branch drafting) ==")
    # engine steps (fixed-shape dispatches) are the machine-independent
    # signal — on a real accelerator fewer dispatches is the win; tiny-
    # model CPU wall clock is dominated by the extra draft dispatches
    import dataclasses
    spec = ServeEngine(model, dataclasses.replace(
        ecfg, speculative="linear", draft_len=3))
    spec.load(params)
    reqs_s = make_requests(cfg)
    toks_s, dt_s, steps_s = drive(spec, reqs_s)
    drafted = max(spec.stats["spec_drafted"], 1)
    assert [r.output for r in reqs_s] == [r.output for r in reqs], \
        "greedy speculative serving must be token-identical"
    print(f"{toks_s} tokens in {steps_s} engine steps "
          f"({steps / steps_s:.2f}x fewer), {dt_s:.2f}s, "
          f"acceptance {spec.stats['spec_accepted'] / drafted:.2f}, "
          "outputs token-identical to plain decode")


if __name__ == "__main__":
    main()
