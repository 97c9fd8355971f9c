"""Share of the traced window in which no operation runs on a device,
averaged over the chips the cell uses: 100 * (1 - busy / window)."""
from bench import trace


def read(rec):
    tr = rec.trace
    if tr is None or not rec.planes or tr.window_ns <= 0:
        return None
    busy = [trace.busy_ns(tr.device_ops[p], tr.window) for p in rec.planes]
    return 100.0 * (1.0 - sum(busy) / len(busy) / tr.window_ns)
