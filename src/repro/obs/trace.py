"""Span/event tracer with Chrome-trace export, bridged to the JAX
profiler's timeline (DESIGN.md §14).

The registry can retarget an op five ways across four scopes and nobody
could *see* it happen: which variant won, what the serve loop spent an
iteration on, where a collective plan fired.  This module is the span
half of the observability plane — :mod:`repro.obs.metrics` is the
aggregate half, :mod:`repro.obs.drift` the calibration-staleness check.

Design constraints (all load-bearing):

* **off-by-default, negligible when off** — ``TRACER.span(...)`` with
  the ring disabled and no profiler session recording is one attribute
  read, one static call and a shared no-op context manager; nothing
  allocates, nothing locks.  Tier-1 timings must not move with the
  tracer compiled in.
* **on the profiler's clock** — while a ``jax.profiler`` session records,
  every span and event is also a ``jax.profiler.TraceAnnotation`` of the
  same name (its args become the annotation's stats), so the program's
  spans land in the ``.xplane.pb`` beside the device's operations.  The
  session is the switch: no flag, no environment variable.  The ring
  keeps its own ``perf_counter_ns`` epoch and does not need the profiler.
* **ring-buffered** — events land in a ``deque(maxlen=capacity)``; a
  long serve run keeps the most recent window instead of growing without
  bound.
* **trace-safe** — span/event attrs are plain host values (strings,
  ints, floats) supplied by the instrumentation sites; the tracer never
  receives or stores jax arrays or tracers.  Sites that run under a jit
  trace (collective plan execution, a blocked() resolve inside
  shard_map) record *per-trace* events — one per compilation, not one
  per device execution — which is exactly what they are.
* **monotonic clocks** — spans time with ``time.perf_counter_ns``;
  :func:`clock` is the interval-timing helper the launchers use in place
  of ``time.time()`` (not monotonic: step timings go negative under
  clock adjustment).

Export is the Chrome trace-event JSON format (``{"traceEvents": [...]}``,
``ph: "X"`` complete events + ``ph: "i"`` instants, microsecond
timestamps), loadable in Perfetto / ``chrome://tracing`` as-is.

    from repro.obs import trace
    trace.TRACER.enable()
    with trace.TRACER.span("serve.decode", active=3):
        ...
    trace.TRACER.save("trace.json")

Enable at import with ``REPRO_TRACE=1`` (capacity override:
``REPRO_TRACE_CAPACITY``).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Any, Iterator, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "TRACER", "clock", "DEFAULT_CAPACITY"]

DEFAULT_CAPACITY = 65536

#: whether a profiler session is recording: one static call, ~50 ns
_profiling = TraceAnnotation.is_enabled


def clock() -> float:
    """Monotonic seconds for interval timing — the drop-in replacement for
    ``time.time()`` pairs in step loops (``time.time()`` is wall clock and
    not monotonic; an NTP adjustment mid-run makes step timings negative).
    Only differences are meaningful."""
    return time.perf_counter()


class _NullSpan:
    """The shared disabled-tracer span: a no-op context manager."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def set(self, **args: Any) -> None:
        pass

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: an annotation on the profiler's timeline while a
    session records (``ann``), and a record in the tracer's ring on exit
    while the ring is enabled (``tracer``); either may be None."""

    __slots__ = ("_tracer", "_ann", "name", "cat", "args", "_t0")

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 args: dict, profiling: bool) -> None:
        self._tracer = tracer
        self._ann = TraceAnnotation(name, **args) if profiling else None
        self.name = name
        self.cat = cat
        self.args = args

    def set(self, **args: Any) -> None:
        """Add args known only after the span opened.  They reach the ring;
        the profiler's annotation took its args when it opened."""
        self.args.update(args)

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
        if self._tracer is not None:
            self._tracer._stack().append(self)
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> bool:
        tracer = self._tracer
        if tracer is not None:
            dur = time.perf_counter_ns() - self._t0
            stack = tracer._stack()
            if stack and stack[-1] is self:
                stack.pop()
            parent = stack[-1].name if stack else None
            tracer._emit(self.name, "X", self._t0, cat=self.cat,
                         dur=dur, args=self.args, parent=parent)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Tracer:
    """Thread-safe span/event recorder with a bounded ring buffer.

    ``enabled`` switches the ring; a recording profiler session switches
    the annotations.  Every instrumentation site checks :meth:`recording`
    (directly or via :meth:`span` returning the shared no-op) before doing
    any work."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY) -> None:
        self.enabled = False
        self._events: deque = deque(maxlen=capacity)
        self._epoch = time.perf_counter_ns()
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.dropped = 0          # events displaced by the ring bound

    # -- lifecycle ----------------------------------------------------------

    def enable(self, capacity: Optional[int] = None) -> None:
        if capacity is not None and capacity != self._events.maxlen:
            with self._lock:
                self._events = deque(self._events, maxlen=capacity)
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._epoch = time.perf_counter_ns()

    @contextlib.contextmanager
    def tracing(self, capacity: Optional[int] = None) -> Iterator["Tracer"]:
        """Scoped enable (tests, one-shot benchmark captures)."""
        prev = self.enabled
        self.enable(capacity)
        try:
            yield self
        finally:
            self.enabled = prev

    # -- recording ----------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _emit(self, name: str, ph: str, t0_ns: int, *, cat: str = "",
              dur: Optional[int] = None, args: Optional[dict] = None,
              parent: Optional[str] = None) -> None:
        ev: dict[str, Any] = {"name": name, "ph": ph,
                              "ts": (t0_ns - self._epoch) / 1e3,
                              "pid": os.getpid(),
                              "tid": threading.get_ident() & 0xFFFFFFFF}
        if cat:
            ev["cat"] = cat
        if dur is not None:
            ev["dur"] = dur / 1e3
        a = dict(args) if args else {}
        if parent:
            a["parent"] = parent
        if a:
            ev["args"] = a
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    def recording(self) -> bool:
        """Whether a span would be recorded anywhere: in the ring, or on
        the timeline of a recording profiler session."""
        return self.enabled or _profiling()

    def span(self, name: str, cat: str = "", **args: Any):
        """A timed span context manager — the no-op singleton when neither
        the ring nor a profiler session records, so call sites never
        branch themselves."""
        profiling = _profiling()
        if not (self.enabled or profiling):
            return _NULL_SPAN
        return _Span(self if self.enabled else None, name, cat, args,
                     profiling)

    def event(self, name: str, cat: str = "", **args: Any) -> None:
        """An instant event (Chrome ``ph: "i"``; an empty annotation on the
        profiler's timeline)."""
        if _profiling():
            with TraceAnnotation(name, **args):
                pass
        if self.enabled:
            self._emit(name, "i", time.perf_counter_ns(), cat=cat,
                       args=args)

    # -- export -------------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        return len(self._events)

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        evs = self.events()
        for ev in evs:
            if ev["ph"] == "i":
                ev.setdefault("s", "t")       # thread-scoped instant
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def save(self, path: str) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


#: Process-global tracer — the one every instrumentation site posts to.
TRACER = Tracer(int(os.environ.get("REPRO_TRACE_CAPACITY",
                                   DEFAULT_CAPACITY)))
if os.environ.get("REPRO_TRACE", "") in ("1", "true"):
    TRACER.enable()
