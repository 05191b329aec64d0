"""Mean occupied slots per engine step (DiffusionEngine.stats
occupancy_sum over engine_steps), over the traced part of the window."""


def read(rec):
    steps = rec.extra["part_steps"]
    return sum(s["occupancy"] for s in steps) / len(steps) if steps else None
