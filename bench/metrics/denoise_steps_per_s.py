"""Request-steps (one denoise step of one video) completed in the window
over its length; the window closes on a device sync after its last
engine step."""


def read(rec):
    return sum(s["request_steps"] for s in rec.steps) / rec.window_s
