"""95th percentile over every request due in the window of the time from
its due time to the end of the engine step that returned its first
token.  A request that never answered counts as missing (infinite)."""
from bench.core import percentile


def read(rec):
    return percentile([r["tokens"][0] - r["due"] if r["tokens"] else None
                       for r in rec.requests], 95)
