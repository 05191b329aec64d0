"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a configuration
(bench/configs/<config>.json) and a traffic mix (bench/traffic/<mix>.json);
its comparison limits are bench/limits/<cell>.json.  The run makes the
weights on the device from the seed, warms the cell's programs (from the
compile cache in <checkout>/.jax_cache), drives the engine for
``--seconds``, and then compares what the window served with the plain
float32 reference.  The last line of stdout is the result:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
     "checks"}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the first part of the window.
Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
for _p in (_ROOT, _ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import core  # noqa: E402
from bench.core import log  # noqa: E402

NO_CHIP = 3


class Tracer:
    """Profiler trace of the first ``seconds`` of the window.  The system
    calls ``start`` before the window opens and, at the first engine step
    past ``seconds``, syncs the device and calls ``stop``."""

    def __init__(self, seconds: float, path: Path, enabled: bool):
        self.seconds, self.path, self.enabled = seconds, path, enabled
        self.active = False
        self.part_s = self.n_steps = None

    def start(self):
        if self.enabled:
            import jax
            shutil.rmtree(self.path, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # the host loop's own calls: noise
            jax.profiler.start_trace(str(self.path), profiler_options=opts)
            self.active = True

    def due(self, elapsed: float) -> bool:
        return self.active and elapsed >= self.seconds

    def stop(self, elapsed: float, n_steps: int):
        import jax
        jax.profiler.stop_trace()
        self.active = False
        self.part_s, self.n_steps = elapsed, n_steps


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str, overrides: dict | None = None):
    spec = core.benchmark_spec()
    cell = core.find_cell(spec, name)
    cfg = core.load_json(core.config_file(spec, cell["config"]))
    cfg.update(cfg.pop("runs_with", {}))    # where the program departs
    cfg["name"] = cell["config"]
    mix = core.load_json(core.BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = core.load_json(core.BENCH / "limits" / f"{name}.json")
    for part, over in (overrides or {}).items():
        {"config": cfg, "traffic": mix, "limits": limits}[part].update(over)
    return spec, cell, cfg, mix, limits


def check_devices(cell: dict) -> str | None:
    """Why this machine cannot run the cell, or None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"needs a TPU, found {devs[0].platform!r}"
    if len(devs) < cell["chips"]:
        return f"needs {cell['chips']} chips, found {len(devs)}"
    return None


def run_cell(name: str, seed: int, seconds: float, trace: int = 0, *,
             require_chip: bool = True, overrides: dict | None = None,
             control: bool = False, compare: bool = True,
             t0: float | None = None, records: list | None = None
             ) -> dict | None:
    """One run of cell ``name``; the result dict, or None without a chip.
    ``control`` also computes the control's numbers (the reference one
    precision below) beside the program's; ``compare=False`` skips the
    comparison (rate sweeps); ``records`` receives the run's Record."""
    spec, cell, cfg, mix, limits = load_cell(name, overrides)
    if importlib.util.find_spec("repro") is None:
        raise SystemExit("bench: the program (src/repro) is not in this "
                         "checkout")
    core.COMPILE_CACHE.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(core.COMPILE_CACHE)
    import jax
    if require_chip:
        why = check_devices(cell)
        if why:
            log(f"{name}: {why}")
            return None
    jax.config.update("jax_compilation_cache_dir", str(core.COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.traffic.gen import Traffic
    clock = core.CompileClock()
    system = core.named_module("systems", cfg["system"])
    sysm = system.System(cfg, mix, seed)
    traffic = Traffic(mix, seed)
    sysm.load()
    sysm.warm(traffic)
    setup_s = time.perf_counter() - (T0 if t0 is None else t0)
    compiles0 = clock.count

    dev = jax.devices()[0]
    peaks = core.peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    rec = core.Record(cell=cell, config=cfg, traffic=mix, seconds=seconds,
                      setup_s=setup_s, peaks=peaks)
    tracer = Tracer(min(mix.get("trace_seconds", seconds), seconds),
                    core.ROOT / ".bench_trace", bool(trace))
    spans = core.Spans()
    spans.tracing = bool(trace)
    sysm.run_window(seconds, traffic, spans, tracer, rec)
    if tracer.active:
        tracer.stop(rec.window_s, sum(not s.get("drain") for s in rec.steps))
    compiles = clock.count - compiles0
    if compiles:
        log(f"{compiles} compilation(s) inside the window")
    peak = sysm.memory_peak() if dev.platform == "tpu" else 0
    if trace:
        from bench import trace as tr
        kernels = [m["name"][:-len("_roofline")]
                   for m in core.cell_metrics(spec, name, "per_layer")
                   if m["name"].endswith("_roofline")]
        rec.trace = tr.reduce_file(tr.find_xplane(str(tracer.path)), kernels)
        shutil.rmtree(tracer.path, ignore_errors=True)
        rec.extra["part_s"] = tracer.part_s
        rec.extra["part_steps"] = rec.steps[:tracer.n_steps]
    else:
        rec.extra["part_s"] = rec.window_s
        rec.extra["part_steps"] = [s for s in rec.steps if not s.get("drain")]
    rec.extra["flops_fn"] = lambda steps: system.model_flops(
        core.Record(cell=cell, config=cfg, traffic=mix, seconds=seconds,
                    steps=steps, extra=rec.extra), cfg)

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in core.cell_metrics(spec, name, section):
        v = core.named_module("metrics", m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    sysm.free()
    if records is not None:
        records.append(rec)
    numbers = sysm.compare("fp32") if compare else {}
    checks = {}
    for key, lim in limits["limits"].items():
        checks[key] = {"value": numbers.get(key), "limit": lim}
    correct = all(c["limit"] is not None and c["value"] is not None
                  and c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": bool(correct),
           "attempted": int(rec.extra["attempted"]),
           "failed": int(rec.extra["failed"]),
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": peak}}
    if trace:
        out["device"]["busy_s"] = rec.trace["busy_s"]
        out["device"]["window_s"] = rec.extra["part_s"]
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    out["compiles_in_window"] = compiles
    if control:
        out["control"] = sysm.compare("fp8")
    out["checks"] = checks
    out["_numbers"] = numbers
    return out


def main(argv=None) -> int:
    args = _args(argv)
    res = run_cell(args.workload, args.seed, args.seconds, args.trace)
    if res is None:
        return NO_CHIP
    numbers = res.pop("_numbers")
    for k, v in numbers.items():
        if k not in res["checks"]:
            log(f"{k} = {v}")
    for k, c in res["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
