"""Share of the traced window in which a collective runs on a device and
no other operation does, averaged over the chips the cell uses:
100 * ``trace.exposed_ns`` / window.  The collectives are XLA's
(``collective-permute``, ``all-reduce``, ``all-gather``, ..., their async
halves too); what overlaps compute costs the window nothing."""
from bench import trace


def read(rec):
    tr = rec.trace
    if tr is None or not rec.planes or tr.window_ns <= 0:
        return None
    exposed = [trace.exposed_ns(tr.device_ops[p], tr.window)
               for p in rec.planes]
    return 100.0 * sum(exposed) / len(exposed) / tr.window_ns
