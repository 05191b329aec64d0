"""Prompt tokens prefilled per engine step (ServeEngine.stats
prefill_tokens deltas), over the traced part of the window."""


def read(rec):
    steps = rec.extra["part_steps"]
    return (sum(s["prefill_tokens"] for s in steps) / len(steps)
            if steps else None)
