"""Bytes of logits the engine copied to the host per engine step
(ServeEngine.stats logits_to_host_bytes: the nbytes of every logits array
the host pulled, over the window, per step of the window).  None where the
engine keeps no such counter."""


def read(rec):
    pulled = rec.counters.get("logits_to_host_bytes")
    n = sum(1 for s in rec.steps if not s.get("drain"))
    return pulled / n if pulled is not None and n else None
