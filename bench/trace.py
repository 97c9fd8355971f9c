"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics read.

A trace is read into plain interval lists (:class:`Trace`); everything
else here is arithmetic on ``(start_ns, end_ns, name)`` tuples, so the tests
can check it on synthetic events as well as on a recorded trace.

- busy: the union of the intervals in which an operation runs on a device,
  clipped to the traced window; idle share is 1 - busy / window.
- per-op time: the summed device durations of each operation name.
- collective exposure: the part of the window in which a collective runs on
  the device and no other operation does.
- idle gaps: the stretches of the window with no device operation, each
  named by the most specific host event that covers its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re

__all__ = ["Trace", "load", "union", "total", "clip", "busy_ns",
           "idle_gaps", "per_op", "exposed_ns", "is_collective", "op_name",
           "WINDOW_SPAN"]

#: the host span the benchmark wraps around the traced window
WINDOW_SPAN = "bench.window"

#: device op names of collectives (XLA's HLO opcodes, async halves too)
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"allgather|allreduce|psum|ppermute", re.IGNORECASE)


@dataclasses.dataclass
class Trace:
    """Device operations per device, host events, and the traced window."""
    device_ops: dict[str, list[tuple[int, int, str]]]
    host: list[tuple[int, int, str]]
    window: tuple[int, int]

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) pairs covering ``intervals``."""
    out: list[list[int]] = []
    for s, e, *_ in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, lo: int, hi: int):
    """``intervals`` cut to [lo, hi]; names kept."""
    out = []
    for s, e, *rest in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, *rest))
    return out


def busy_ns(ops, window) -> int:
    """Time in ``window`` in which some operation of ``ops`` runs."""
    return total(clip(ops, *window))


def _complement(merged, lo, hi):
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_gaps(ops, host, window) -> list[tuple[int, int, str]]:
    """Stretches of ``window`` with no operation of ``ops``, each named by
    the shortest host event covering its middle ("" where none does)."""
    gaps = _complement(union(clip(ops, *window)), *window)
    host = sorted(host)
    active: list[tuple[int, int, str]] = []     # heap of (length, end, name)
    out, j = [], 0
    for s, e in gaps:                           # middles ascend
        mid = (s + e) // 2
        while j < len(host) and host[j][0] <= mid:
            hs, he, name = host[j]
            heapq.heappush(active, (he - hs, he, name))
            j += 1
        # every event in the heap began by now, so the shortest one that
        # has not ended covers this middle (and ended ones stay ended)
        while active and active[0][1] <= mid:
            heapq.heappop(active)
        out.append((s, e, active[0][2] if active else ""))
    return out


def per_op(ops, window) -> dict[str, int]:
    """Summed device nanoseconds per operation name inside ``window``."""
    out: dict[str, int] = {}
    for s, e, name in clip(ops, *window):
        out[name] = out.get(name, 0) + (e - s)
    return out


def exposed_ns(ops, window) -> int:
    """Time in ``window`` in which a collective runs and nothing else."""
    ops = clip(ops, *window)
    coll = union(o for o in ops if is_collective(o[2]))
    rest = union(o for o in ops if not is_collective(o[2]))
    out = 0
    for s, e in coll:
        covered = total(clip(rest, s, e))
        out += (e - s) - covered
    return out


#: ops that only contain others (a while loop spans its whole body)
_CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(event_name: str) -> str:
    """The HLO op's name from a trace event's text (``%name = ...``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _device_op_line(plane):
    """The line of a device plane that holds one event per operation."""
    for line in plane.lines:
        if line.name == "XLA Ops":
            return line
    return None


def load(path: str, window_span: str = WINDOW_SPAN) -> Trace:
    """Read the ``.xplane.pb`` under ``path`` (a file or a trace directory).

    Device planes are ``/device:<KIND>:<n>``; their "XLA Ops" line gives
    one event per operation, named here by its HLO op name; ops that only
    contain others (while loops, conditionals, calls) are left out.  The
    window is the benchmark's own
    ``window_span`` host event."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    device_ops, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            line = _device_op_line(plane)
            if line is not None:
                device_ops[plane.name] = [
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), name)
                    for e in line.events
                    if not _CONTAINER.match(name := op_name(e.name))]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((int(e.start_ns),
                             int(e.start_ns + e.duration_ns), e.name)
                            for e in line.events)
    spans = [h for h in host if h[2] == window_span]
    if not spans:
        raise ValueError(f"trace has no {window_span!r} host span")
    window = (min(h[0] for h in spans), max(h[1] for h in spans))
    return Trace(device_ops=device_ops, host=host, window=window)
