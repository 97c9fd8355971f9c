"""The Pallas DIA SpMV kernel's share of its HBM roofline.

Bytes: the algorithmic (ndiags + 2) * 4 B per row of one SpMV (every stored
diagonal entry, x and y), counted from shapes by the entry, times the
kernel's launches in the traced window.  Time: the summed device durations
of the kernel's events.  Share = bytes / (time * HBM peak), averaged over
the cell's chips.  It counts the work, not what the kernel reads: the
kernel reads x three times and its wrapper pads x first."""
import re

from bench import trace

#: the kernel's name in the device trace
KERNEL = re.compile(r"spmv_dia")


def read(rec):
    tr = rec.trace
    bytes_per_call = rec.stats.get("spmv_bytes")
    if tr is None or not rec.planes or not bytes_per_call:
        return None
    shares = []
    for plane in rec.planes:
        events = [(s, e) for s, e, name in trace.clip(
            tr.device_ops[plane], *tr.window) if KERNEL.search(name)]
        if not events:
            continue
        seconds = sum(e - s for s, e in events) / 1e9
        shares.append(100.0 * len(events) * bytes_per_call
                      / (seconds * rec.peaks.hbm_bytes_per_s))
    return sum(shares) / len(shares) if shares else None
