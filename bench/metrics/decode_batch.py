"""Mean of ServeEngine.step()'s return value (occupied slots) over the
engine steps of the traced part of the window."""


def read(rec):
    steps = rec.extra["part_steps"]
    return sum(s["occupied"] for s in steps) / len(steps) if steps else None
