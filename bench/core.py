"""Shared pieces of the benchmark harness: where things live, how a cell's
files are found by name, the host spans and the compile clock, and the
record that the per-layer metric readers read.

Everything that belongs to one configuration, traffic mix, metric or
kernel sits in a file of its own and is found here by its name:

    bench/configs/<config>.json     sizes, cut, engine settings, limits
    bench/traffic/<mix>.json        parameters read by bench/traffic/gen.py
    bench/metrics/<name>.py         reader: record -> number or None
                                    (falls back to <name before '.'>.py)
    bench/costs/<kernel>.py         operations and bytes of one kernel
    bench/systems/<system>.py       drives one engine kind ("system" key)
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# One fixed path inside the checkout, so only a cell's first run compiles.
COMPILE_CACHE = ROOT / ".jax_cache"


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config_file(spec: dict, config: str) -> Path:
    for c in spec["configs"]:
        if c["name"] == config:
            return ROOT / c["file"]
    raise SystemExit(f"bench: no config {config!r} in BENCHMARK.json")


def load_module(path: Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


_MODULES: dict = {}


def named_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, else the file of the part of ``name``
    before its first dot (``engine_step_ms.chat`` -> ``engine_step_ms``).
    Each file is loaded once per process."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / kind / f"{stem}.py"
        if path.exists():
            if path not in _MODULES:
                _MODULES[path] = load_module(
                    path, f"bench_{kind}_{stem}".replace(".", "_"))
            return _MODULES[path]
    raise FileNotFoundError(f"bench/{kind}/{name}.py")


def cell_metrics(spec: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` that ``cell`` reports."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation; a missing
    value (None, a request that never answered) sorts above all others."""
    if not values:
        return None
    xs = sorted(float("inf") if v is None else float(v) for v in values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == float("inf"):
        return float("inf") if pos > lo or xs[lo] == float("inf") else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


class Spans:
    """Host spans around the benchmark's calls into the engine.  Each span
    is kept in memory (name, start, end on ``time.perf_counter``) and, while
    a profiler trace runs, also written into it as a TraceAnnotation so the
    trace reduction can label the device's idle gaps."""

    def __init__(self):
        self.items: list = []
        self.tracing = False

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, owner: Spans, name: str):
        self.owner, self.name, self.ann = owner, name, None

    def __enter__(self):
        if self.owner.tracing:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.owner.items.append((self.name, self.t0, t1))
        return False


class CompileClock:
    """Counts JAX compilations (trace, lowering, backend compile or
    persistent-cache load) while armed: the measured window must hold
    none."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.count += 1
            self.seconds += duration


@dataclass
class Record:
    """What one run saw, for the metric readers.

    ``steps``: one dict per engine step in the window (``t0``/``t1`` host
    clock, counters' deltas, what the step served); ``requests``: one dict
    per request the window counts (due, submit and token times);
    ``trace``: the reduced profiler trace (``--trace 1`` only)."""
    cell: dict
    config: dict
    traffic: dict
    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: list = field(default_factory=list)
    requests: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    trace: Optional[dict] = None
    peaks: Optional[dict] = None
    extra: dict = field(default_factory=dict)


def peaks_for(device_kind: str) -> dict:
    """Published peaks of ``device_kind`` (bench/peaks.json); an unknown
    kind is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["chips"]:
        raise SystemExit(f"bench: no published peaks for {device_kind!r}")
    return table["chips"][device_kind]
