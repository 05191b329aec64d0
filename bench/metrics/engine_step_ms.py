"""Host time per engine step: the benchmark's spans around eng.step()
and the closing device syncs, over the steps of the traced part of the
window, in milliseconds.  The DiT engine returns before the device has
finished a step, so its time shows in the later steps' spans and the
syncs; the total is the window's."""


def read(rec):
    end = rec.extra["part_s"]
    n = len(rec.extra["part_steps"])
    t = sum(b - a for name, a, b in rec.extra["spans"]
            if name in ("bench.step", "bench.sync") and b <= end + 1e-9)
    return t / n * 1e3 if n else None
