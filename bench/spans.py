"""The program's own host spans in a reduced trace (:class:`bench.trace.Trace`).

``repro.obs.trace`` puts every span of the program on the profiler's
timeline while a session records, under the span's own name, so the
benchmark's traced window holds them beside the device's operations.
Readers here take durations within the host plane only: never a host
timestamp minus a device one, whose planes need not share an epoch.
"""
from __future__ import annotations

__all__ = ["durations_ns", "mean_us"]


def durations_ns(tr, name: str) -> list[int]:
    """Durations of the host events named exactly ``name`` that lie wholly
    inside the traced window."""
    lo, hi = tr.window
    return [e - s for s, e, n in tr.host if n == name and lo <= s and e <= hi]


def mean_us(tr, name: str):
    """Mean duration in microseconds of the ``name`` spans in the window;
    None where the window holds none (a program without the span)."""
    if tr is None:
        return None
    d = durations_ns(tr, name)
    return sum(d) / len(d) / 1e3 if d else None
