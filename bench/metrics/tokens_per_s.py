"""Prompt tokens prefilled plus tokens generated in the window, over its
length."""


def read(rec):
    generated = sum(1 for r in rec.requests for t in r["tokens"]
                    if t <= rec.window_s)
    return (rec.counters["prefill_tokens"] + generated) / rec.window_s
