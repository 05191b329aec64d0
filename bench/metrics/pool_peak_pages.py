"""KV pages the engine held at once (ServeEngine.stats pool_peak_pages:
the pool's size less the low-water mark of its free list), at the end of
the run.  The pool is sized from it, and the pool's size sets what each
step copies."""


def read(rec):
    return rec.extra.get("pool_peak_pages")
