"""95th percentile of every gap between successive tokens of every
request due in the window, in milliseconds."""
from bench.core import percentile


def read(rec):
    gaps = [b - a for r in rec.requests
            for a, b in zip(r["tokens"], r["tokens"][1:])]
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3
