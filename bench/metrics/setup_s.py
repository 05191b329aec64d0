"""Process start to window start: weights, compile or cache load, warm-up."""


def read(rec):
    return rec.setup_s
