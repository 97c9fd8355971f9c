"""Microseconds per CG iteration: the measured window's host time per call
over the mean iterations per call."""


def read(rec):
    it = rec.stats.get("iterations")
    if not it or not rec.calls:
        return None
    return rec.window_s / rec.calls / it * 1e6
