"""Find an open-loop cell's knee: the highest arrival rate the system
sustains, by one sweep over fixed rates in one process.

    python bench/sweep.py --workload <cell> --rates 3,5,7 --seconds 30

For each rate: time to first token (median, p95) over the requests due in
the first and the last third of the window (a sustained rate keeps them
alike; past the knee the queue grows and the last third waits longer),
the gap between tokens (p95) and tokens served per second.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from bench import run  # noqa: E402
from bench.core import percentile  # noqa: E402


def summary(rec, rate: float) -> dict:
    T = rec.window_s
    def ttft(lo, hi):
        v = [r["tokens"][0] - r["due"] if r["tokens"] else None
             for r in rec.requests if lo <= r["due"] < hi]
        return percentile(v, 50), percentile(v, 95), len(v)
    gaps = [b - a for r in rec.requests
            for a, b in zip(r["tokens"], r["tokens"][1:])]
    served = sum(1 for r in rec.requests for t in r["tokens"] if t <= T)
    return {"rate": rate, "requests": len(rec.requests),
            "ttft_first_third": ttft(0, T / 3), "ttft_last_third":
            ttft(2 * T / 3, T), "ttft_p95": percentile(
                [r["tokens"][0] - r["due"] if r["tokens"] else None
                 for r in rec.requests], 95),
            "itl_p95_ms": (percentile(gaps, 95) or 0) * 1e3,
            "tokens_per_s": served / T,
            "prefill_tokens_per_s": rec.counters["prefill_tokens"] / T,
            "steps": len(rec.steps)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        recs = []
        t = time.perf_counter()
        res = run.run_cell(args.workload, args.seed, args.seconds, 0,
                           overrides={"traffic": {"rate": rate}},
                           compare=False, t0=t, records=recs)
        if res is None:
            return run.NO_CHIP
        print(json.dumps(summary(recs[0], rate)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
