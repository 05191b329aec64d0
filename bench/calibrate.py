"""Readings for the comparison limits, many seeds in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20
        [--control] [--out results/calibrate.jsonl]

Each seed is one run of the cell (run.run_cell) with a short window; it
prints the program's compared numbers and, with ``--control``, the
control's: the reference computed one precision below the configuration's
(bf16 -> fp8).  The lower reading of a limit is the largest program
number over the seeds, the upper the smallest control number.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = run.run_cell(args.workload, seed, args.seconds, args.trace,
                           control=args.control, t0=t)
        if res is None:
            return run.NO_CHIP
        line = {"workload": args.workload, "seed": seed,
                "numbers": res.pop("_numbers"),
                "control": res.pop("control", None),
                "run_s": time.perf_counter() - t, **res}
        print(json.dumps(line), flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
