"""Device idle time per SpMV call that the program's host work does not
account for: (window - device busy) / n - mean ``dispatch:spmv_dia`` span,
n the number of those spans in the traced window.

In a closed loop the device idles from one call's last operation to the
next call's first: the runtime's completion and wake-up of the waiting
host, the harness's own bookkeeping, the next dispatch, and the launch.
Taking the dispatch out leaves the runtime's part (plus the harness's few
microseconds a call).  Both terms are durations within one plane, so an
offset between the host's and the device's clocks cannot bias it, and a
host-side tracer's inflation adds to both and largely cancels.  Device
busy is averaged over the cell's chips."""
from bench import spans, trace

SPAN = "dispatch:spmv_dia"


def read(rec):
    tr = rec.trace
    if tr is None or not rec.planes or tr.window_ns <= 0:
        return None
    dispatch = spans.durations_ns(tr, SPAN)
    if not dispatch:
        return None
    busy = [trace.busy_ns(tr.device_ops[p], tr.window) for p in rec.planes]
    idle = tr.window_ns - sum(busy) / len(busy)
    return (idle - sum(dispatch)) / len(dispatch) / 1e3
