"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy time (the union of the intervals in which
an operation ran), each kernel's time, the operations that took most
time, and the device's idle gaps labelled by the benchmark's host span
that covers most of each gap.

Planes: a device is a plane named ``/device:<KIND>:<n>``; its operations
are the events of its ``XLA Ops`` line.  Host spans are events named
``bench.*`` on any ``/host:`` plane.  Both carry nanosecond timestamps on
one clock.
"""
from __future__ import annotations

import bisect
import glob
import os

GAP_MIN_NS = 20_000          # gaps shorter than 20 us are dispatch jitter


def _union(intervals):
    """Merged (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _matches(ev, prefixes, memo):
    """The kernel prefix an op event belongs to, from its name or, once
    per distinct name, its string stats (HLO op metadata)."""
    name = ev.name
    if name in memo:
        return memo[name]
    hit = None
    texts = [name]
    try:
        texts += [v for _, v in ev.stats if isinstance(v, str)]
    except Exception:                               # stats not readable
        pass
    for p in prefixes:
        if any(p in t for t in texts):
            hit = p
            break
    memo[name] = hit
    return hit


def reduce_file(path: str, kernels=()) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), kernels)


def reduce(pd, kernels=()) -> dict:
    """{"devices", "busy_s" (mean over devices), "span_s", "kernels":
    {prefix: {"time_s", "count"}}, "device_ops": [[name, s]], "idle_gaps":
    [[label, s]], "ops": n}."""
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name.upper():
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append(list(line.events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "kernels": {}, "device_ops": [],
                "idle_gaps": [], "ops": 0, "span_s": 0.0}
    memo, kern = {}, {p: {"time_s": 0.0, "count": 0} for p in kernels}
    by_name, busy, gaps, n_ops = {}, [], [], 0
    lo = min(ev.start_ns for evs in devices for ev in evs) if any(devices) \
        else 0
    hi = max(ev.start_ns + ev.duration_ns for evs in devices for ev in evs) \
        if any(devices) else 0
    for evs in devices:
        evs = sorted(evs, key=lambda ev: (ev.start_ns, -ev.duration_ns))
        ivs = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in evs]
        for i, ev in enumerate(evs):
            n_ops += 1
            # a loop or call op encloses the ops it runs: count leaves only
            if i + 1 < len(evs) and ivs[i + 1][0] < ivs[i][1]:
                continue
            name = short_name(ev.name)
            by_name[name] = by_name.get(name, 0.0) + ev.duration_ns
            p = _matches(ev, kernels, memo) if kernels else None
            if p is not None:
                kern[p]["time_s"] += ev.duration_ns * 1e-9
                kern[p]["count"] += 1
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                 if b[0] - a[1] >= GAP_MIN_NS]
    for p in kern:
        kern[p]["time_s"] /= len(devices)
    spans.sort()
    starts = [a for a, _, _ in spans]
    labelled = []
    for s, e in gaps:
        # host spans come from one thread and do not overlap: the ones
        # that cover part of the gap are consecutive
        best, label = 0, "host.other"
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(spans) and spans[i][0] < e:
            a, b, name = spans[i]
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, label = ov, name
            i += 1
        labelled.append([label, (e - s) * 1e-9])
    labelled.sort(key=lambda x: -x[1])
    top = sorted(by_name.items(), key=lambda x: -x[1])[:10]
    return {"devices": len(devices), "busy_s": sum(busy) / len(busy),
            "span_s": (hi - lo) * 1e-9, "kernels": kern,
            "device_ops": [[n, t * 1e-9 / len(devices)] for n, t in top],
            "idle_gaps": labelled[:10], "ops": n_ops}


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)
