"""Share of the traced window in which no operation ran on the device:
1 - busy / window, in percent (busy: union of the device's op intervals
in the profiler trace, averaged over the chips used)."""


def read(rec):
    if not rec.trace or not rec.trace["devices"]:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.extra["part_s"])
