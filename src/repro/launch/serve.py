"""Serving CLI: batched generation through the slot engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \
        --requests 6 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.launch.cache import use_compile_cache
from repro.models.api import build_model
from repro.serve import EngineConfig, Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    model = build_model(cfg)
    if model.decode is None or model.kind == "dit":
        raise SystemExit(f"{args.arch} has no token decode path")

    params = model.init(jax.random.PRNGKey(args.seed))
    ecfg = EngineConfig(max_slots=args.slots, max_len=args.max_len)
    # every LM family serves paged: attention K/V pages, MLA latent pages,
    # recurrent state checkpoints
    eng = ServeEngine(model, ecfg)
    eng.load(params)
    rng = np.random.default_rng(args.seed)
    reqs = []
    for uid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size,
                              size=rng.integers(4, 17)).astype(np.int32)
        r = Request(uid=uid, prompt=prompt, max_new_tokens=args.max_new)
        reqs.append(r)
        eng.submit(r)

    t0 = time.perf_counter()
    steps = 0
    while True:
        active = eng.step()
        steps += 1
        if active == 0 and not eng._queue:
            break
        if steps > args.requests * (args.max_new + 4):
            break
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output or []) for r in reqs)
    print(f"[serve] {args.arch}: {args.requests} requests, "
          f"{total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / max(dt, 1e-9):.1f} tok/s, {steps} engine steps)")
    for r in reqs[:3]:
        print(f"  req {r.uid}: prompt[:6]={r.prompt[:6].tolist()} "
              f"-> out[:8]={(r.output or [])[:8]}")


if __name__ == "__main__":
    main()
