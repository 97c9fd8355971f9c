"""``blocked()`` — the pad-to-block / call / unpad combinator, with a
persistent per-(op, shape, dtype) block-size autotuning cache.

Every Pallas kernel wants block-aligned inputs; every wrapper used to
hand-roll the same ``round_up``/``jnp.pad``/slice dance with hardcoded 128s.
``blocked()`` centralises it:

    inner(*padded_args, blocks={dim: size}, interpret=...)  -> padded output
    blocked('matmul', inner,
            pad={0: ('m', 'k'), 1: ('k', 'n')},   # arg index -> dim per axis
            out=('m', 'n'),                        # output axes to slice back
            defaults={'m': 128, 'n': 128, 'k': 128},
            candidates=(...,))                     # autotune search space

Block sizes come from, in priority order: explicit per-call overrides, the
autotune cache (``results/autotune.json``, path override via
``REPRO_AUTOTUNE_CACHE``), and the defaults.  When ``REPRO_AUTOTUNE=1`` and
there is no cache entry for (op, shape, dtype), the candidates are measured
on the spot with the real arguments and the winner is persisted — ArBB's
"optimise for the target architecture detected at runtime", made sticky.
Measurement is skipped under a jax trace (timings there would be
meaningless) — the defaults are then cached *marked* (``_default``) so a
later eager resolve, or the autotune sweep's ``premeasure`` hook, upgrades
them with a real measurement instead of pinning defaults forever — and any
candidate that fails to compile is simply dropped.

Cache keys carry the ambient *mesh* (DESIGN.md §8):

    op|dims|dtype|scope|mesh         e.g. matmul|k=32,m=256,n=96|float32|
                                          mesh|pod2xdata2xmodel2

A mesh-scoped variant dispatches the chip kernel per shard *inside*
shard_map, where the best blocks depend on the local shard shape and the
collective schedule — so entries tuned on one chip must never silently
serve a sharded call (and vice versa).  Legacy three-part keys from older
caches are upgraded to ``|chip|-`` on load, with a one-line note logged.
"""
from __future__ import annotations

import functools
import json
import logging
import os
import threading
import time
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

__all__ = ["round_up", "AutotuneCache", "get_cache", "autotune_enabled",
           "ambient_scope_key", "resolve_blocks", "blocked", "premeasure",
           "upgrade_legacy_keys", "PREMEASURE", "DEFAULT_CACHE_PATH"]

DEFAULT_CACHE_PATH = os.path.join("results", "autotune.json")


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def upgrade_legacy_keys(raw: Mapping[str, dict]) -> tuple[dict, int]:
    """Upgrade pre-mesh three-part keys (``op|dims|dtype``) to the modern
    five-part scheme (``...|chip|-``).  Modern keys load first and legacy
    keys merge via ``setdefault``, so a stale pre-mesh entry never clobbers
    a fresher chip entry.  Shared by the block cache and the cost model
    (:mod:`repro.core.costmodel`), which persist side by side under the
    same key scheme."""
    data: dict[str, dict] = {k: v for k, v in raw.items()
                             if k.count("|") != 2}
    legacy = 0
    for k, v in raw.items():
        if k.count("|") == 2:            # pre-mesh schema: op|dims|dtype
            data.setdefault(f"{k}|chip|-", v)
            legacy += 1
    return data, legacy


def ambient_scope_key() -> tuple[str, str]:
    """The (scope, mesh) components of the autotune key right now:
    ``('chip', '-')`` on one chip, ``('mesh', 'pod2xdata2xmodel2')`` under
    an ambient O3/O4 mesh — so per-shard tuning inside shard_map never
    aliases chip entries of the same local shape."""
    from repro.core import registry      # lazy: keep blocking importable alone

    ctx = registry.select_context()
    if ctx.scope != "mesh" or ctx.topology is None:
        return "chip", "-"
    return "mesh", ctx.topology.describe()


class AutotuneCache:
    """JSON-backed block-size cache: key -> {dim: block, '_seconds': t}."""

    def __init__(self, path: Optional[str] = None) -> None:
        self.path = path or os.environ.get("REPRO_AUTOTUNE_CACHE",
                                           DEFAULT_CACHE_PATH)
        self._data: Optional[dict[str, dict]] = None
        self._lock = threading.Lock()

    @staticmethod
    def key(op: str, dims: Mapping[str, int], dtype: str,
            scope: str = "chip", mesh: str = "-") -> str:
        shape = ",".join(f"{k}={v}" for k, v in sorted(dims.items()))
        return f"{op}|{shape}|{dtype}|{scope}|{mesh}"

    @staticmethod
    def parse_key(key: str) -> tuple[str, dict[str, int], str, str, str]:
        """Invert :meth:`key`: ``(op, dims, dtype, scope, mesh)``."""
        op, shape, dtype, scope, mesh = key.split("|")
        dims = {}
        if shape:
            for part in shape.split(","):
                k, v = part.split("=")
                dims[k] = int(v)
        return op, dims, dtype, scope, mesh

    def _load(self) -> dict[str, dict]:
        if self._data is None:
            try:
                with open(self.path) as f:
                    raw = json.load(f)
            except (FileNotFoundError, json.JSONDecodeError):
                raw = {}
            data, legacy = upgrade_legacy_keys(raw)
            if legacy:
                logging.getLogger(__name__).info(
                    "autotune cache %s: upgraded %d legacy key(s) to chip "
                    "scope (op|dims|dtype -> op|dims|dtype|chip|-); "
                    "mesh-scoped calls re-tune instead of silently reusing "
                    "chip blocks", self.path, legacy)
            self._data = data
        return self._data

    def lookup(self, key: str) -> Optional[dict[str, int]]:
        """The cached blocks for ``key`` (measurement metadata stripped)."""
        entry = self._load().get(key)
        if entry is None:
            return None
        return {k: int(v) for k, v in entry.items() if not k.startswith("_")}

    def entry(self, key: str) -> Optional[dict]:
        """The raw entry including metadata (``_seconds``, ``_default``)."""
        entry = self._load().get(key)
        return dict(entry) if entry is not None else None

    def pending_defaults(self) -> list[str]:
        """Keys whose blocks were pinned *without* measurement (a trace was
        ambient when they resolved) — what the sweep's eager premeasure hook
        upgrades (DESIGN.md §11)."""
        return sorted(k for k, v in self._load().items()
                      if isinstance(v, dict) and v.get("_default"))

    def put(self, key: str, blocks: Mapping[str, int],
            seconds: Optional[float] = None, default: bool = False) -> None:
        with self._lock:
            data = self._load()
            entry: dict[str, Any] = {k: int(v) for k, v in blocks.items()}
            if seconds is not None:
                entry["_seconds"] = round(seconds, 9)
            if default:
                # unmeasured defaults, pinned under a trace: marked so a
                # later eager resolve re-measures instead of hitting forever
                entry["_default"] = True
            data[key] = entry
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            tmp = f"{self.path}.tmp"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=2, sort_keys=True)
            os.replace(tmp, self.path)


_cache: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    """The process cache, re-opened if ``REPRO_AUTOTUNE_CACHE`` changed
    (lets tests point it at a temp file)."""
    global _cache
    path = os.environ.get("REPRO_AUTOTUNE_CACHE", DEFAULT_CACHE_PATH)
    if _cache is None or _cache.path != path:
        _cache = AutotuneCache(path)
    return _cache


def autotune_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "") in ("1", "true", "measure")


def resolve_blocks(
    op: str,
    dims: Mapping[str, int],
    dtype: str,
    defaults: Mapping[str, int],
    candidates: Sequence[Mapping[str, int]] = (),
    measure: Optional[Callable[[Mapping[str, int]], float]] = None,
) -> dict[str, int]:
    """Cache hit > fresh measurement (when enabled and possible) > defaults.

    ``measure(blocks) -> seconds`` runs one candidate; pass None when timing
    is impossible (e.g. under a trace).  The cache key carries the ambient
    scope/mesh (see :func:`ambient_scope_key`): inside a shard_map variant
    the entry is tuned per shard shape *and* per mesh shape.

    With autotune enabled but a trace ambient, the defaults are cached
    *marked* (``_default``) rather than silently pinned: a mesh-scoped
    first call is always inside shard_map tracing, so an unmarked entry
    would freeze the defaults forever.  A later eager resolve of the same
    key — a chip call, or the sweep's :func:`premeasure` hook — sees the
    marker and upgrades the entry with a real measurement."""
    cache = get_cache()
    key = AutotuneCache.key(op, dims, dtype, *ambient_scope_key())
    raw = cache.entry(key)
    can_measure = bool(autotune_enabled() and candidates
                       and measure is not None)
    if raw is not None and not (raw.get("_default") and can_measure):
        obs_metrics.METRICS.counter(f"blocking.cache_hit.{op}").inc()
        hit = {k: int(v) for k, v in raw.items() if not k.startswith("_")}
        return {**defaults, **hit}
    obs_metrics.METRICS.counter(f"blocking.cache_miss.{op}").inc()
    if can_measure:
        best: Optional[dict[str, int]] = None
        best_t = float("inf")
        with obs_trace.TRACER.span(f"blocking.autotune:{op}", cat="blocking",
                                   op=op, key=key):
            for cand in (defaults, *candidates):
                merged = {**defaults, **cand}
                try:
                    t = measure(merged)
                except Exception:
                    continue              # candidate doesn't compile: skip
                if t < best_t:
                    best, best_t = merged, t
        if best is not None:
            cache.put(key, best, seconds=best_t)
            obs_trace.TRACER.event("blocking.measured", cat="blocking",
                                   op=op, key=key, seconds=best_t)
            return best
    if autotune_enabled() and measure is None and candidates and raw is None:
        cache.put(key, defaults, default=True)
        obs_trace.TRACER.event("blocking.default_marked", cat="blocking",
                               op=op, key=key)
    return dict(defaults)


def _dims_of(args: Sequence[Any],
             pad: Mapping[int, Sequence[Optional[str]]]) -> dict[str, int]:
    dims: dict[str, int] = {}
    for i, spec in pad.items():
        for axis, dname in enumerate(spec):
            if dname is not None:
                dims.setdefault(dname, args[i].shape[axis])
    return dims


def _is_tracing(args: Sequence[Any]) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in args)


#: op -> eager premeasure hook, registered by :func:`blocked` — the sweep's
#: way to measure a (dims, scope, mesh) block entry *outside* any trace with
#: concrete shard-shaped arguments (DESIGN.md §11).
PREMEASURE: dict[str, Callable] = {}


def premeasure(op: str, *args: Any, interpret: bool = False) -> dict[str, int]:
    """Eagerly measure op's block candidates on ``args`` under the ambient
    scope key, upgrading a default-marked entry.  ``args`` must be concrete
    (the whole point is escaping the trace)."""
    if op not in PREMEASURE:
        raise LookupError(f"op {op!r} has no blocked() combinator; "
                          f"premeasurable: {sorted(PREMEASURE)}")
    return PREMEASURE[op](*args, interpret=interpret)


def blocked(
    op: str,
    inner: Callable,
    *,
    pad: Mapping[int, Sequence[Optional[str]]],
    out: Sequence[Optional[str]],
    defaults: Mapping[str, int],
    candidates: Sequence[Mapping[str, int]] = (),
    measure_iters: int = 2,
) -> Callable:
    """Wrap ``inner`` (which demands block-aligned shapes) into a function of
    unaligned arrays.  See the module docstring for the spec."""
    pad = {i: tuple(spec) for i, spec in pad.items()}
    out = tuple(out)
    defaults = dict(defaults)

    @functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
    def padded_call(*args, blocks, interpret):
        bl = dict(blocks)
        dims = _dims_of(args, pad)
        padded = []
        for i, a in enumerate(args):
            spec = pad.get(i)
            if spec is None:
                padded.append(a)
                continue
            widths = [(0, 0) if d is None
                      else (0, round_up(a.shape[ax], bl[d]) - a.shape[ax])
                      for ax, d in enumerate(spec)]
            padded.append(jnp.pad(a, widths))
        res = inner(*padded, blocks=bl, interpret=interpret)
        sl = tuple(slice(None) if d is None else slice(0, dims[d])
                   for d in out)
        return res[sl]

    def _measure(args, interpret):
        def run(blocks: Mapping[str, int]) -> float:
            key = tuple(sorted(blocks.items()))
            jax.block_until_ready(
                padded_call(*args, blocks=key, interpret=interpret))
            t0 = time.perf_counter()
            for _ in range(measure_iters):
                r = padded_call(*args, blocks=key, interpret=interpret)
            jax.block_until_ready(r)
            return (time.perf_counter() - t0) / measure_iters
        return run

    def wrapped(*args, interpret: bool = False,
                overrides: Optional[Mapping[str, Optional[int]]] = None):
        pinned = {k: int(v) for k, v in (overrides or {}).items()
                  if v is not None}
        if set(pinned) >= set(defaults):
            bl = pinned                  # fully pinned: nothing to resolve
        else:
            dims = _dims_of(args, pad)
            tracing = _is_tracing(args)
            measure = None if tracing else _measure(args, interpret)
            with obs_trace.TRACER.span(f"blocked.resolve:{op}",
                                       cat="blocking", op=op,
                                       traced=tracing):
                bl = resolve_blocks(op, dims, str(args[0].dtype), defaults,
                                    candidates, measure)
            bl.update(pinned)
        blocks = tuple(sorted(bl.items()))
        tracer = obs_trace.TRACER
        if not tracer.recording():       # attrs are built lazily on purpose
            return padded_call(*args, blocks=blocks, interpret=interpret)
        with tracer.span(f"blocked.pad_call:{op}", cat="blocking", op=op,
                         blocks=",".join(f"{k}={v}" for k, v in blocks)):
            return padded_call(*args, blocks=blocks, interpret=interpret)

    def premeasure_op(*args, interpret: bool = False) -> dict[str, int]:
        """Eager block measurement with these concrete args under the
        *ambient* scope key — call inside ``use_level(O3/O4, mesh)`` with
        shard-local shapes to fill the mesh-scoped entries a traced
        shard_map dispatch could only default-mark."""
        if _is_tracing(args):
            raise ValueError(f"premeasure({op!r}) needs concrete (eager) "
                             "arrays; it exists to escape the trace")
        dims = _dims_of(args, pad)
        return resolve_blocks(op, dims, str(args[0].dtype), defaults,
                              candidates, _measure(args, interpret))

    wrapped.padded_call = padded_call
    wrapped.premeasure = premeasure_op
    PREMEASURE[op] = premeasure_op
    return wrapped
