"""The whole call's share of the chip's peak: the least time the chip could
take for one call -- the larger of its FLOPs over the FLOP peak and its
HBM bytes over the HBM bandwidth, both counted from shapes by the entry --
over the measured window's host time per call.  ``mfu.call`` in the SpMV
cell, ``mfu.solve`` in the CG cell."""
from bench import peaks


def read(rec):
    if rec.peaks is None or not rec.calls:
        return None
    least = peaks.least_seconds(rec.work, rec.peaks)
    return 100.0 * least / (rec.window_s / rec.calls)
