"""Unified operator registry: one retargeting plane for ExecLevel × backend.

The paper's defining property is that *the program text never changes* —
ArBB retargets the same source at runtime via ``ARBB_OPT_LEVEL`` /
``ARBB_NUM_CORES`` (paper §3).  This module is that property, generalised:
every operator (``matmul``, ``spmv_ell``, ``fft``, ``flash_attention``, the
solver SpMV formulations, ...) registers *variants*, and a single
:func:`dispatch` picks one from the ambient :class:`~repro.core.execlevel.
ExecContext`, the hardware platform, and the requested backend plane.

Vocabulary (DESIGN.md §1):

    plane     a retargeting plane — how a kernel body executes:
              'pallas' (Mosaic-compiled, TPU), 'interpret' (pallas_call in
              interpret mode, the test harness), 'xla' (pure-jnp reference).
              The plane knob is ``use_backend()`` / the ``REPRO_KERNELS``
              env var — the ArBB_OPT_LEVEL of the kernel layer.
    variant   (op, name, impl, plane?, available?, accepts?, cost) — one
              implementation of an op.  DSL-level variants (e.g. the solver
              SpMV formulations spmv1/spmv2/ell/dia) have ``plane=None``:
              they are jnp programs and run under any plane.
    scope     how far a variant reaches: 'chip' (one device — every kernel
              and DSL formulation the paper ports) or 'mesh' (a shard_map
              program spanning the ambient mesh's 'data' axis — the
              ARBB_NUM_CORES story taken past the shared-memory ceiling,
              DESIGN.md §7).  Mesh-scoped variants are only admissible when
              an O3/O4 mesh is ambient, and then they are *preferred*.
    available(ctx)     capability predicate over (ExecLevel, mesh, platform)
    accepts(*args)     per-call predicate over concrete arguments (shapes,
                       layouts) — e.g. the DIA formulation only accepts DIA
                       matrices, flash kernels need block-divisible lengths
    cost      static preference hint; lower wins among admissible variants.
              Named tiers live on :class:`Cost`; when the measured cost
              model (repro.core.costmodel, DESIGN.md §11) holds whole-call
              seconds for this shape class, those outrank the static prior

Selection rules (DESIGN.md §6):

    1. ``dispatch(op, ..., variant=name)`` — explicit, always honoured.
    2. Otherwise variants are ordered (scope-match-first,
       requested-plane-first, cost, name) and the first one that is
       *available* on this context AND *accepts* the arguments wins.
       Scope outranks the plane request: under an active mesh a sharded
       formulation beats any single-chip kernel, exactly as ArBB O3 beats
       O2 without the program text changing.
    3. A requested plane that this hardware cannot run (e.g. 'pallas'
       off-TPU) is an error: a run that asked for the chip must not
       quietly measure or validate another plane.  A *variant* whose
       accepts() rejects the arguments still degrades to the next one (the
       ``dispatch.falloff`` counter records it), and a mesh-scoped variant
       without an ambient mesh (or whose shapes don't divide the mesh)
       degrades to the chip formulation.

Providers register lazily: ops are declared here by module path and imported
on first dispatch, so upper layers (models, serve) depend only on this
module, never on kernel modules.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import os
import threading
import time
from typing import Any, Callable, Iterator, Optional

import jax

from repro.core import execlevel
from repro.core.topology import MeshTopology, topology_of
from repro.obs import drift as obs_drift
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

__all__ = ["Variant", "SelectContext", "OperatorRegistry", "REGISTRY",
           "select_context", "Cost",
           "register", "unregister", "dispatch", "select", "explain",
           "variants", "ops",
           "use_backend", "requested_backend", "resolve_backend", "PLANES",
           "SCOPES"]

#: The kernel retargeting planes (ordered by preference on TPU).
PLANES = ("pallas", "interpret", "xla")


class Cost:
    """Named static cost tiers — the one fallback source of truth behind the
    calibrated cost model (DESIGN.md §11).

    Every hand-maintained ``cost=`` ladder (kernels/ops.py, sparse/spmm.py,
    numerics/spmv.py) derives from these constants instead of repeating raw
    floats; when the cost model holds measured seconds for a shape class,
    these priors are only the tie-break for uncalibrated variants.

    Plane tiers: ``BLOCKSPARSE`` (tile-skipping kernel, admissible only when
    its accepts() density gate passes — DESIGN.md §12) < ``PALLAS``
    (compiled kernel, production) < ``XLA_CHUNKED`` (streamed jnp schedule)
    < ``XLA`` (plain jnp reference) < ``ORACLE`` (always-correct, never-fast
    baseline) << ``INTERPRET`` (test harness).  Sparse-layout ranks
    (``DIA`` < ``BSR`` < ``ELL`` < ``CSR``) mirror the format selector's
    strongest-first ordering; :meth:`formulation` offsets a rank into a
    plane tier so per-format variant triples keep their relative order
    across planes."""

    BLOCKSPARSE = 0.75
    PALLAS = 1.0
    XLA_CHUNKED = 1.5
    XLA = 2.0
    ORACLE = 20.0
    INTERPRET = 100.0

    # sparse-layout formulation ranks (selector's strongest-first ordering)
    DIA = 4.0
    BSR = 5.0
    ELL = 6.0
    CSR = ORACLE

    @staticmethod
    def formulation(rank: float, plane: Optional[str] = None) -> float:
        """A formulation rank offset into its plane's tier: pallas (and
        DSL-level ``plane=None``) = rank, xla = rank + 0.5, interpret =
        ``INTERPRET`` + rank."""
        if plane == "xla":
            return rank + 0.5
        if plane == "interpret":
            return Cost.INTERPRET + rank
        return rank

#: The selection scopes: one device vs the ambient O3/O4 mesh.
SCOPES = ("chip", "mesh")

#: op name -> modules that register its variants on import (chip kernels
#: first, then the mesh-scoped shard_map formulations).
_PROVIDERS = {
    "matmul": ("repro.kernels.ops", "repro.distributed.numerics"),
    "spmv_ell": ("repro.kernels.ops",),
    "spmv_dia": ("repro.kernels.ops",),
    "fft": ("repro.kernels.ops", "repro.distributed.numerics"),
    "flash_attention": ("repro.kernels.ops", "repro.distributed.attention"),
    "flash_attention_state": ("repro.kernels.ops",),
    "paged_attention": ("repro.kernels.ops", "repro.distributed.attention"),
    "chunk_attention": ("repro.kernels.ops",),
    "solver_spmv": ("repro.numerics.spmv", "repro.distributed.numerics",
                    "repro.sparse.spmm"),
    "spmm": ("repro.sparse.spmm", "repro.distributed.numerics"),
    "spgemm": ("repro.sparse.spgemm", "repro.distributed.numerics"),
}

#: provider modules already imported (an op's chip module may register it
#: before its mesh module has run; membership is per-module, not per-op).
_loaded_providers: set = set()


@dataclasses.dataclass(frozen=True)
class SelectContext:
    """What variant selection may look at: level × mesh × hardware × scope
    × mesh *topology* (axis names, sizes, roles — DESIGN.md §8), so a
    variant can predicate on mesh rank and axis roles, not just on whether
    a mesh exists.  E.g. ``mesh_psum_2d`` requires a non-degenerate model
    axis; the hierarchical CG plan requires a pod axis."""
    level: execlevel.ExecLevel
    mesh: Optional[Any]
    platform: str           # jax.default_backend(): 'tpu' | 'cpu' | 'gpu'
    scope: str = "chip"     # 'mesh' when an O3/O4 mesh is ambient
    topology: Optional[MeshTopology] = None

    @property
    def mesh_rank(self) -> int:
        """Non-degenerate mesh axes (0 with no mesh) — a (8, 1) mesh has
        rank 1, a (2, 2, 2) mesh rank 3."""
        return self.topology.rank if self.topology is not None else 0


def select_context() -> SelectContext:
    """The context variant selection sees right now."""
    ctx = execlevel.current()
    scope = "mesh" if ctx.is_distributed else "chip"
    return SelectContext(level=ctx.level, mesh=ctx.mesh,
                         platform=jax.default_backend(), scope=scope,
                         topology=topology_of(ctx.mesh))


def _plane_available(plane: Optional[str], ctx: SelectContext) -> bool:
    if plane == "pallas":
        return ctx.platform == "tpu"
    return True          # 'interpret', 'xla', and DSL-level (None) run anywhere


def _has_tracer(args: tuple, kwargs: dict) -> bool:
    """Whether any argument is a jax tracer — drift timing (and anything
    else host-side) must never run under an ambient trace."""
    return (any(isinstance(a, jax.core.Tracer) for a in args)
            or any(isinstance(v, jax.core.Tracer)
                   for v in kwargs.values()))


def _attach_out_sharding(v: "Variant", ctx: Optional["SelectContext"],
                         args: tuple, kwargs: dict, out: Any) -> Any:
    """Attach the variant's decided output layout to the result as an
    advisory ``out_sharding`` attribute (DESIGN.md §15).  ``ctx`` may be
    None (the pinned-variant path never built one); it is only computed
    when the variant actually declares a hook.  Attachment is best-effort:
    a result type without settable attributes just returns unannotated —
    the decision is advisory, never load-bearing for correctness."""
    if v.out_sharding is None:
        return out
    if ctx is None:
        ctx = select_context()
    sh = v.decide_out_sharding(ctx, args, kwargs)
    if sh is None:
        return out
    try:
        object.__setattr__(out, "out_sharding", sh)
    except (AttributeError, TypeError):
        pass
    return out


@dataclasses.dataclass(frozen=True)
class Variant:
    op: str
    name: str
    impl: Callable
    plane: Optional[str] = None
    scope: str = "chip"
    cost: float = 10.0
    available: Optional[Callable[[SelectContext], bool]] = None
    accepts: Optional[Callable[..., bool]] = None
    #: optional ``out_sharding(ctx, *args, **kwargs) -> NamedSharding|None``
    #: — the output layout this variant *decides* (the first consumer: mesh
    #: SpGEMM, whose product comes back block-sharded so a chained op never
    #: reshards, DESIGN.md §15).  dispatch() attaches the decision to the
    #: result as an advisory ``out_sharding`` attribute and explain()
    #: surfaces it per candidate row.
    out_sharding: Optional[Callable[..., Any]] = None
    doc: str = ""

    def decide_out_sharding(self, ctx: SelectContext, args: tuple,
                            kwargs: dict) -> Optional[Any]:
        """The sharding this variant would leave the output in for this
        call, or None (no declaration / the hook declined or raised —
        a layout *decision* must never break the dispatch that carries
        it)."""
        if self.out_sharding is None:
            return None
        try:
            return self.out_sharding(ctx, *args, **kwargs)
        except Exception:
            return None

    def is_available(self, ctx: SelectContext) -> bool:
        if not _plane_available(self.plane, ctx):
            return False
        if self.scope == "mesh" and ctx.scope != "mesh":
            return False        # a shard_map program needs an ambient mesh
        return self.available(ctx) if self.available is not None else True

    def matches(self, *args: Any, **kwargs: Any) -> bool:
        return self.accepts(*args, **kwargs) if self.accepts is not None \
            else True


# ---------------------------------------------------------------------------
# requested backend plane (the scoped ARBB_OPT_LEVEL of the kernel layer)
# ---------------------------------------------------------------------------

_state = threading.local()


def requested_backend() -> Optional[str]:
    """The explicitly requested plane (context manager beats env), if any.

    A mistyped ``REPRO_KERNELS`` fails loudly here rather than silently
    running the default plane."""
    req = getattr(_state, "plane", None)
    if req is not None:
        return req
    env = os.environ.get("REPRO_KERNELS") or None
    if env is not None and env not in PLANES:
        raise ValueError(f"REPRO_KERNELS={env!r} is not a backend plane; "
                         f"choose from {PLANES}")
    return env


@contextlib.contextmanager
def use_backend(name: str) -> Iterator[str]:
    """Scoped plane request.  ``repro.kernels.ops.backend`` is this."""
    if name not in PLANES:
        raise ValueError(f"unknown backend plane {name!r}; choose from {PLANES}")
    prev = getattr(_state, "plane", None)
    _state.plane = name
    try:
        yield name
    finally:
        _state.plane = prev


def _check_request(req: Optional[str], ctx: SelectContext) -> None:
    """Raise when the requested plane cannot run on this hardware."""
    if req is not None and not _plane_available(req, ctx):
        raise RuntimeError(
            f"backend plane {req!r} was requested but the platform is "
            f"{ctx.platform!r}; it needs a TPU")


def resolve_backend() -> str:
    """The plane dispatch will favour right now: the requested plane, else
    the platform default ('pallas' on TPU, 'xla' elsewhere).  A request this
    hardware cannot run (e.g. 'pallas' off-TPU) raises."""
    ctx = select_context()
    req = requested_backend()
    _check_request(req, ctx)
    if req is not None:
        return req
    return "pallas" if ctx.platform == "tpu" else "xla"


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

class OperatorRegistry:
    def __init__(self) -> None:
        self._ops: dict[str, dict[str, Variant]] = {}
        self._lock = threading.Lock()

    # -- registration -------------------------------------------------------

    def register(self, op: str, name: str, impl: Optional[Callable] = None, *,
                 plane: Optional[str] = None, scope: str = "chip",
                 cost: float = 10.0,
                 available: Optional[Callable[[SelectContext], bool]] = None,
                 accepts: Optional[Callable[..., bool]] = None,
                 out_sharding: Optional[Callable[..., Any]] = None,
                 doc: str = ""):
        """Register a variant.  Usable directly or as a decorator."""
        if impl is None:
            def deco(fn: Callable) -> Callable:
                self.register(op, name, fn, plane=plane, scope=scope,
                              cost=cost, available=available, accepts=accepts,
                              out_sharding=out_sharding, doc=doc)
                return fn
            return deco
        if plane is not None and plane not in PLANES:
            raise ValueError(f"unknown plane {plane!r} for {op}/{name}")
        if scope not in SCOPES:
            raise ValueError(f"unknown scope {scope!r} for {op}/{name}; "
                             f"choose from {SCOPES}")
        with self._lock:
            table = self._ops.setdefault(op, {})
            if name in table:
                raise ValueError(
                    f"duplicate variant {name!r} for op {op!r}; "
                    f"unregister it first to replace")
            table[name] = Variant(op=op, name=name, impl=impl, plane=plane,
                                  scope=scope, cost=cost, available=available,
                                  accepts=accepts, out_sharding=out_sharding,
                                  doc=doc or impl.__doc__ or "")
        return impl

    def unregister(self, op: str, name: Optional[str] = None) -> None:
        """Drop one variant, or the whole op when ``name`` is None."""
        with self._lock:
            if name is None:
                self._ops.pop(op, None)
            else:
                self._ops.get(op, {}).pop(name, None)

    # -- lookup -------------------------------------------------------------

    def _table(self, op: str) -> dict[str, Variant]:
        for module in _PROVIDERS.get(op, ()):
            if module not in _loaded_providers:
                # mark loaded only on success: a failed provider import must
                # stay loud on retry, not silently drop its variants forever
                importlib.import_module(module)
                _loaded_providers.add(module)
        if op not in self._ops:
            raise LookupError(f"unknown op {op!r}; registered: "
                              f"{sorted(self._ops)}")
        return self._ops[op]

    def ops(self) -> list[str]:
        return sorted(set(self._ops) | set(_PROVIDERS))

    def variants(self, op: str) -> tuple[Variant, ...]:
        return tuple(sorted(self._table(op).values(),
                            key=lambda v: (v.cost, v.name)))

    def get(self, op: str, name: str) -> Variant:
        table = self._table(op)
        if name not in table:
            raise ValueError(f"op {op!r} has no variant {name!r}; "
                             f"registered: {sorted(table)}")
        return table[name]

    @staticmethod
    def _scope_mesh(ctx: SelectContext) -> tuple[str, str]:
        """The (scope, mesh) key components of the ambient context — the
        cost model's and the drift detector's shared vocabulary."""
        if ctx.scope == "mesh" and ctx.topology is not None:
            return "mesh", ctx.topology.describe()
        return "chip", "-"

    def _calibrated(self, op: str, args: tuple, kwargs: dict,
                    ctx: SelectContext,
                    table: dict[str, Variant]) -> dict[str, float]:
        """Measured whole-call seconds per variant from the cost model
        (DESIGN.md §11) — ``{}`` when the model is absent, uncalibrated for
        this shape class, or holds fewer than two of this op's variants (a
        singleton measurement must not promote the one variant that
        happened to be measured)."""
        from repro.core import costmodel      # lazy: keep import graph thin

        scope, mesh = self._scope_mesh(ctx)
        measured = costmodel.get_model().seconds_for(
            op, args, kwargs, scope=scope, mesh=mesh)
        if len(set(measured) & set(table)) < 2:
            return {}
        return measured

    def _ranked(self, op: str, args: tuple, kwargs: dict,
                ctx: SelectContext, req: Optional[str],
                table: dict[str, Variant]
                ) -> tuple[list[Variant], dict[str, float]]:
        """All variants of ``op`` in selection order, plus the calibrated
        seconds that shaped the order — the single ranking both
        :meth:`select` and :meth:`explain` consume, so they cannot
        diverge."""
        measured = self._calibrated(op, args, kwargs, ctx, table) \
            if req is None else {}
        # Scope match outranks the plane request: under an active mesh the
        # sharded formulation wins (ARBB_NUM_CORES reborn as mesh shape);
        # without one, mesh variants are unavailable and chip order is
        # exactly what it always was.  Calibrated variants rank first, by
        # measured seconds — the cost model is keyed by the ambient
        # scope/mesh, so mesh and chip variants measured under the same
        # context compare on observed time, not on the scope heuristic.
        ranked = sorted(
            table.values(),
            key=lambda v: ((0, measured[v.name]) if v.name in measured
                           else (1, 0.0),
                           0 if v.scope == ctx.scope else 1,
                           0 if (req is not None and v.plane == req) else 1,
                           v.cost, v.name))
        return ranked, measured

    def _select(self, op: str, args: tuple, kwargs: dict
                ) -> tuple[Variant, SelectContext, int]:
        """The winner, the context it won under, and its rank index —
        rank > 0 means higher-ranked candidates were rejected (a
        degradation fall-off: ring→chip, 2-D→1-D, pallas→xla)."""
        ctx = select_context()
        req = requested_backend()
        _check_request(req, ctx)
        table = self._table(op)
        ranked, _ = self._ranked(op, args, kwargs, ctx, req, table)
        for i, v in enumerate(ranked):
            if v.is_available(ctx) and v.matches(*args, **kwargs):
                return v, ctx, i
        raise LookupError(
            f"no variant of op {op!r} is available for platform "
            f"{ctx.platform!r} and these arguments; registered: "
            f"{[v.name for v in ranked]}")

    def select(self, op: str, *args: Any, variant: Optional[str] = None,
               **kwargs: Any) -> Variant:
        """Pick the variant :func:`dispatch` would run (without running it).

        Precedence (DESIGN.md §6 + §11): explicit ``variant=`` pin > scope
        match > requested plane > **calibrated cost** (measured seconds
        from the cost model for this shape class/scope/mesh, which also
        outrank scope when present — observed roofline position beats the
        mesh-first heuristic) > static ``cost=`` prior > name.  An
        explicitly requested plane (``use_backend`` / ``REPRO_KERNELS``)
        disables calibrated re-ranking: the knob is an instruction, the
        model a measurement."""
        if variant is not None:
            return self.get(op, variant)
        return self._select(op, args, kwargs)[0]

    def explain(self, op: str, *args: Any, variant: Optional[str] = None,
                **kwargs: Any) -> list[dict]:
        """The full ranked candidate table for this call, without
        executing anything (DESIGN.md §14).

        One row per variant in selection order.  Each carries the ranking
        inputs (``cost``, ``calibrated_seconds``, ``source``) and the
        verdict: ``selected`` on exactly one row (the variant
        :meth:`dispatch` would run — same ranking, same predicates), and
        on every loser a ``reason``:

            plane-unavailable       requested hardware plane absent here
            scope-mismatch          mesh-scoped variant, no ambient mesh
            available-predicate     ``available(ctx)`` said no
            accepts-predicate       ``accepts(*args)`` said no (includes
                                    the block-sparse density gate)
            outranked-by-calibration  admissible, but a measured variant
                                    ranked ahead (§11)
            outranked               admissible, beaten on static order
            no-variant-selected     every candidate rejected (the
                                    LookupError dispatch would raise)

        A predicate that *raises* is reported as a rejection with the
        exception inline rather than propagating — explain is a
        diagnostic and must survive what it diagnoses."""
        ctx = select_context()
        req = requested_backend()
        table = self._table(op)
        if variant is not None:
            pin = self.get(op, variant)
            pin_sh = pin.decide_out_sharding(ctx, args, kwargs)
            return [{"op": op, "rank": 0, "variant": pin.name,
                     "plane": pin.plane, "scope": pin.scope,
                     "cost": pin.cost, "calibrated_seconds": None,
                     "source": "pinned", "selected": True,
                     "out_sharding": str(pin_sh) if pin_sh is not None
                     else None,
                     "reason": "selected: explicit variant= pin"}]
        ranked, measured = self._ranked(op, args, kwargs, ctx, req, table)
        scope, mesh = self._scope_mesh(ctx)
        rows: list[dict] = []
        winner_calibrated = False
        have_winner = False
        for i, v in enumerate(ranked):
            sh = v.decide_out_sharding(ctx, args, kwargs)
            row = {"op": op, "rank": i, "variant": v.name,
                   "plane": v.plane, "scope": v.scope, "cost": v.cost,
                   "calibrated_seconds": measured.get(v.name),
                   "source": "calibrated" if v.name in measured
                   else "static",
                   "out_sharding": str(sh) if sh is not None else None,
                   "level": ctx.level.name, "ambient_scope": scope,
                   "mesh": mesh, "selected": False}
            if not _plane_available(v.plane, ctx):
                row["reason"] = (f"plane-unavailable: {v.plane!r} is not "
                                 f"available on {ctx.platform!r}")
            elif v.scope == "mesh" and ctx.scope != "mesh":
                row["reason"] = ("scope-mismatch: mesh-scoped variant "
                                 "without an ambient O3/O4 mesh")
            else:
                try:
                    ok = v.available(ctx) if v.available is not None \
                        else True
                    why = "available-predicate: rejected this context " \
                          f"(level={ctx.level.name}, mesh={mesh})"
                except Exception as e:          # diagnose, don't die
                    ok, why = False, ("available-predicate raised "
                                      f"{type(e).__name__}: {e}")
                if ok:
                    try:
                        ok = v.matches(*args, **kwargs)
                        why = "accepts-predicate: rejected these " \
                              "arguments" + (f" — {v.doc}" if v.doc
                                             else "")
                    except Exception as e:
                        ok, why = False, ("accepts-predicate raised "
                                          f"{type(e).__name__}: {e}")
                if not ok:
                    row["reason"] = why
                elif not have_winner:
                    have_winner = True
                    winner_calibrated = v.name in measured
                    row["selected"] = True
                    row["reason"] = "selected: first admissible in rank " \
                        "order" + (" (calibrated)" if winner_calibrated
                                   else "")
                else:
                    row["reason"] = ("outranked-by-calibration: admissible,"
                                     " but a measured variant ranked ahead"
                                     if winner_calibrated and
                                     v.name not in measured
                                     else "outranked: admissible, beaten "
                                     "on rank order")
            rows.append(row)
        if not have_winner and rows:
            for row in rows:
                row["no_variant_selected"] = True
        return rows

    def dispatch(self, op: str, *args: Any, variant: Optional[str] = None,
                 **kwargs: Any) -> Any:
        """Select (per the module docstring's rules) and invoke.

        Instrumented (DESIGN.md §14): per-(op, variant) selection counts
        and fall-off counts are always on (two dict bumps); spans while
        the tracer's ring is enabled or a profiler session records
        (:meth:`_dispatch_traced`); whole-call drift timing only under
        :func:`repro.obs.drift.collect` with concrete arguments — the
        ``block_until_ready`` it needs is a host sync no default path
        ever pays."""
        if variant is not None:
            v = self.get(op, variant)
            obs_metrics.METRICS.counter(f"dispatch.{op}.{v.name}").inc()
            return _attach_out_sharding(v, None, args, kwargs,
                                        v.impl(*args, **kwargs))
        if obs_trace.TRACER.recording() or obs_drift.collecting():
            return self._dispatch_traced(op, args, kwargs)
        v, ctx, rank = self._select(op, args, kwargs)
        _count(op, v, rank)
        return _attach_out_sharding(v, ctx, args, kwargs,
                                    v.impl(*args, **kwargs))

    def _dispatch_traced(self, op: str, args: tuple, kwargs: dict) -> Any:
        """:meth:`dispatch` while tracing.  ``dispatch:<op>`` spans the
        whole dispatch from entry; inside it ``dispatch.select:<op>`` spans
        :meth:`_select` (context, ranking, cost-model lookup, predicates)
        and ``dispatch.invoke:<op>`` the variant's call (the jitted call
        and its launch) with the output-layout attachment.  The variant's
        args go on ``dispatch:<op>`` in the ring and on
        ``dispatch.invoke:<op>`` everywhere: a profiler annotation takes
        its args when it opens, before the variant is known."""
        tracer = obs_trace.TRACER
        with tracer.span(f"dispatch:{op}", cat="dispatch", op=op) as span:
            with tracer.span(f"dispatch.select:{op}", cat="dispatch"):
                v, ctx, rank = self._select(op, args, kwargs)
            _count(op, v, rank)
            scope, mesh = self._scope_mesh(ctx)
            info = {"variant": v.name, "plane": str(v.plane),
                    "scope": v.scope, "level": ctx.level.name, "mesh": mesh}
            span.set(**info)
            if rank > 0:
                tracer.event("dispatch.falloff", cat="dispatch", op=op,
                             variant=v.name, rank=rank)
            with tracer.span(f"dispatch.invoke:{op}", cat="dispatch",
                             **info):
                if obs_drift.collecting() and not _has_tracer(args, kwargs):
                    t0 = time.perf_counter()
                    out = jax.block_until_ready(v.impl(*args, **kwargs))
                    obs_drift.DETECTOR.observe(
                        op, v.name, time.perf_counter() - t0, args, kwargs,
                        scope=scope, mesh=mesh)
                else:
                    out = v.impl(*args, **kwargs)
                return _attach_out_sharding(v, ctx, args, kwargs, out)


def _count(op: str, v: Variant, rank: int) -> None:
    """The always-on selection counters of one dispatch."""
    obs_metrics.METRICS.counter(f"dispatch.{op}.{v.name}").inc()
    if rank > 0:
        # a higher-ranked candidate was rejected: the degradation ladder
        # in action (ring→chip, 2-D→1-D, pallas→xla, ...)
        obs_metrics.METRICS.counter(f"dispatch.falloff.{op}").inc()


#: Process-global registry instance — the single retargeting plane.
REGISTRY = OperatorRegistry()

register = REGISTRY.register
unregister = REGISTRY.unregister
dispatch = REGISTRY.dispatch
select = REGISTRY.select
explain = REGISTRY.explain
variants = REGISTRY.variants
ops = REGISTRY.ops
