"""One run of one benchmark cell: set-up, measured window, optional traced
window, correctness check, result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by name:

    BENCHMARK.json               cells, metrics, run length
    bench/configs/<config>.json  the deployment: generator, grid, chips, ...
    bench/traffic/<mix>.json     what the window drives: entry and its knobs
    bench/cells/<cell>.json      the limits of the numbers compared
    bench/entries/<entry>.py     the timed call, its work count, its check
    bench/generators/<gen>.py    seeded data on the device, plain reference
    bench/metrics/<metric>.py    ``read(record) -> float | None``; a metric
                                 split by cells, ``<base>.<part>``, may
                                 share ``<base>.py``

The loop is closed: one caller issues a call, waits for its result, then
issues the next, until the window's seconds have passed; the window ends
on a call boundary.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

__all__ = ["ROOT", "Spec", "Work", "Record", "spec", "load_module",
           "reader", "run", "NoAccelerator"]


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Work:
    """Work of one call, counted from shapes."""
    flops: float
    hbm_bytes: float


@dataclasses.dataclass
class Spec:
    """A cell with everything found for it by name."""
    root: str
    workload: dict
    config: dict
    traffic: dict
    cell: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    run_seconds: int

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


@dataclasses.dataclass
class Record:
    """What a run saw; the per-layer readers take their numbers from it."""
    spec: Spec
    calls: int
    window_s: float
    dispatch_ns: list[int]
    stats: dict
    work: Work
    peaks: Any = None
    trace: Any = None
    planes: list = dataclasses.field(default_factory=list)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    mod_name = f"bench_{kind}_{name}".replace(".", "_")
    if mod_name in sys.modules and getattr(
            sys.modules[mod_name], "__file__", None) == path:
        return sys.modules[mod_name]
    loader = importlib.util.spec_from_file_location(mod_name, path)
    if loader is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod = importlib.util.module_from_spec(loader)
    sys.modules[mod_name] = mod
    loader.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str, reported: set[str]) -> bool:
    """A metric with ``workloads`` is read in those cells; one without, in
    every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric["moves"] in reported


def reader(root: str, metric: str):
    """The reader of per-layer metric ``metric``: ``bench/metrics/<metric>.py``,
    or, for a metric split by cells as ``<base>.<part>``, the shared
    ``bench/metrics/<base>.py``."""
    base = metric.split(".", 1)[0]
    name = metric if os.path.exists(
        os.path.join(root, "bench", "metrics", f"{metric}.py")) else base
    return load_module(root, "metrics", name)


def spec(workload: str, root: str = ROOT) -> Spec:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = dict(_read_json(os.path.join(root, configs[w["config"]]["file"])))
    traffic = _read_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    cell = _read_json(os.path.join(root, "bench", "cells", f"{workload}.json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Spec(root=root, workload=w, config=config, traffic=traffic,
                cell=cell, end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@functools.cache
def _compile_events() -> list[int]:
    """A counter of JAX trace/compile events, registered once per process."""
    import jax

    seen = [0]

    def listener(event: str, *_: Any, **__: Any) -> None:
        if event.startswith("/jax/core/compile/"):
            seen[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def _enable_compile_cache(root: str) -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_accelerator: bool = True):
    """The first ``chips`` devices; raises :class:`NoAccelerator` where JAX
    finds no accelerator or fewer chips."""
    import jax

    devs = jax.devices()
    if require_accelerator and devs[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX has "
                            f"{len(devs)}")
    return devs[:chips]


def _memory_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class _Sample:
    """A seeded reservoir of ``size`` (call index, output) pairs."""

    def __init__(self, seed: int, size: int):
        self.rng = random.Random(seed)
        self.size = size
        self.items: list = []
        self.seen = 0

    def offer(self, i: int, out: Any) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append((i, out))
            return
        j = self.rng.randrange(self.seen)
        if j < self.size:
            self.items[j] = (i, out)


def _window(cell, seconds: float, sample: _Sample, start: int,
            annotate: bool = False):
    """Closed-loop calls until ``seconds`` have passed; returns
    (calls, elapsed s, dispatch ns per call, stats outputs)."""
    import jax

    clock = time.perf_counter_ns
    dispatch, outs = [], []
    i = start
    if annotate:
        span = jax.profiler.TraceAnnotation
    t0 = clock()
    end = t0 + int(seconds * 1e9)
    while True:
        if annotate:
            with span("bench.call"):
                a = clock()
                out = cell.call(i)
                b = clock()
            with span("bench.wait"):
                jax.block_until_ready(out)
        else:
            a = clock()
            out = cell.call(i)
            b = clock()
            jax.block_until_ready(out)
        dispatch.append(b - a)
        outs.append(cell.stat_of(out))
        sample.offer(i, cell.answer(out))
        del out
        i += 1
        if clock() >= end:
            break
    return i - start, (clock() - t0) / 1e9, dispatch, outs


def _traced(cell, seconds: float, start: int, seed: int):
    """A short window under the profiler; returns its reduced trace."""
    import jax
    from bench import trace as trace_mod

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                _window(cell, seconds, _Sample(seed, 0), start,
                        annotate=True)
        finally:
            jax.profiler.stop_trace()
        return trace_mod.load(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _checks(numbers: dict, limits: dict) -> tuple[dict, int]:
    """(``{name: {value, limit}}`` with each value the worst answer's,
    number of answers over a limit)."""
    if set(numbers) != set(limits):
        raise KeyError(f"compared numbers {sorted(numbers)} and limits "
                       f"{sorted(limits)} differ")
    per_answer = zip(*(numbers[k] for k in sorted(numbers)))
    failed = sum(1 for row in per_answer
                 if any(not v <= limits[k]
                        for k, v in zip(sorted(numbers), row)))
    return {k: {"value": max(numbers[k]), "limit": limits[k]}
            for k in sorted(numbers)}, failed


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, spec_: Optional[Spec] = None,
        require_accelerator: bool = True, control: bool = False,
        log: Callable[[str], None] = lambda s: print(s, file=sys.stderr),
        ) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``control`` puts the entry's plain reference, in the precision below the
    configuration's, in the program's place (the check has to fail it)."""
    t_start = time.perf_counter()
    s = spec_ or spec(workload, root)
    import jax

    devs = devices_for(s.chips, require_accelerator)
    _enable_compile_cache(root)
    compiles = _compile_events()
    from bench import peaks as peaks_mod

    kind = devs[0].device_kind
    peaks = peaks_mod.lookup(kind) if require_accelerator else None
    phases = {"init_s": time.perf_counter() - t_start}
    entry = load_module(root, "entries", s.traffic["entry"])
    cell = entry.build(s.config, s.traffic, seed, devs, root=root,
                       control=control)
    phases["data_s"] = time.perf_counter() - t_start - sum(phases.values())
    cell.warm()
    ran = cell.variants()
    print(f"variants: {json.dumps(ran, sort_keys=True)}", flush=True)
    setup_s = time.perf_counter() - t_start
    phases["warm_s"] = setup_s - sum(phases.values())
    log(f"setup: {json.dumps(phases, sort_keys=True)}")

    sample = _Sample(seed, int(s.traffic.get("sample", 8)))
    before = compiles[0]
    calls, window_s, dispatch, outs = _window(cell, seconds, sample, 0)
    in_window = compiles[0] - before
    if in_window:
        raise RuntimeError(f"{in_window} trace/compile events inside the "
                           "measured window")
    memory_peak = _memory_peak(devs)
    stats = cell.stats(outs)
    record = Record(spec=s, calls=calls, window_s=window_s,
                    dispatch_ns=dispatch, stats=stats,
                    work=cell.work(stats), peaks=peaks)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        tr = _traced(cell, float(s.traffic.get("trace_seconds", 2.0)),
                     calls, seed)
        record.trace = tr
        record.planes = used = _used_planes(tr, devs)
        from bench import trace as trace_mod
        busy = [trace_mod.busy_ns(tr.device_ops[p], tr.window) for p in used]
        device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        device["window_s"] = tr.window_ns / 1e9
        breakdown = _breakdown(tr, used)

    log(f"stats: {json.dumps(stats, sort_keys=True)}")
    kept = sample.items
    del outs
    cell.free_program()
    checks, failed = _checks(cell.check(kept), s.cell["limits"])
    correct = failed == 0 and bool(kept)

    if trace:
        metrics = {}
        for m in s.per_layer:
            value = reader(root, m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        per_call_ms = window_s / calls * 1e3
        values = {"setup_s": setup_s, "call_ms": per_call_ms,
                  "solve_ms": per_call_ms}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in s.end_to_end}
    out = {"correct": correct, "attempted": calls, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    return out


def _used_planes(tr, devs) -> list[str]:
    """Trace planes of the devices the cell used."""
    ids = {d.id for d in devs}
    planes = []
    for name in sorted(tr.device_ops):
        try:
            idx = int(name.rsplit(":", 1)[1])
        except ValueError:
            continue
        if idx in ids:
            planes.append(name)
    return planes


def _breakdown(tr, used) -> dict:
    from bench import trace as trace_mod

    if not used:
        return {"device_ops": [], "idle_gaps": []}
    ops = trace_mod.per_op(tr.device_ops[used[0]], tr.window)
    gaps: dict[str, int] = {}
    for s, e, name in trace_mod.idle_gaps(tr.device_ops[used[0]], tr.host,
                                          tr.window):
        gaps[name] = gaps.get(name, 0) + (e - s)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"device_ops": top(ops), "idle_gaps": top(gaps)}
