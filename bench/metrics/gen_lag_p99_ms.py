"""How late the load generator submitted: 99th percentile of submit time
minus due time over the requests submitted in the traced part of the
window, in milliseconds."""
from bench.core import percentile


def read(rec):
    end = rec.extra["part_s"]
    lags = [r["submit"] - r["due"] for r in rec.requests if r["submit"] <= end]
    p = percentile(lags, 99)
    return None if p is None else p * 1e3
