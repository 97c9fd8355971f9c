"""Hierarchical collectives plane (DESIGN.md §8): axis-role-aware
reduction plans for the mesh-scoped numerics.

PR 2's distributed numerics reduce over one literal axis name (``psum(x,
'data')``): correct on an O3 ``(data, model)`` mesh, but on an O4 ``(pod,
data, model)`` mesh the pod axis either computes replicated or — worse for
a naive port — joins a *flat* reduction that treats slow inter-pod DCN hops
and fast intra-pod ICI hops identically.  That is the single-level-reduction
wall the DBCSR Xeon Phi port hit before moving to 2-D block distributions
(PAPERS.md), and the gradient path here already avoids it (reduce-scatter
intra-pod, all-reduce inter-pod — DESIGN.md §4).

This module gives the numerics plane the same structure.  A
:class:`ReducePlan` is built from the ambient mesh's *topology* (axis names,
sizes, roles — :mod:`repro.core.topology`) and emits **hierarchical
schedules**:

    psum          partial -> psum over data axes (intra-pod) -> psum over
                  pod axes (inter-pod)
    psum_scatter  reduce-scatter over the data axes, then all-reduce over
                  the pod axes: every participant ends with its shard of
                  the fully-reduced result, and only already-reduced data
                  crosses the pod boundary
    all_gather    gather intra-pod first, then inter-pod — the dual of the
                  sharding order, so row shards reassemble in global order
    halo          the rows just before and just after a row shard, from
                  its two neighbours in the same pod-major order: two
                  ``ppermute`` shifts, no gather

Plans are frozen/hashable, so shard_map executables cache per plan
(``lru_cache``) exactly as the PR 2 kernels cached per mesh.  On an O3 mesh
with no pod axis every schedule degenerates to the flat single-axis form —
the plan layer costs nothing when the hierarchy is trivial.

The sequence-parallel plane (DESIGN.md §10) adds the *ring* schedule:
:func:`ring_plan` emits a :class:`RingPlan` over the same batch-role axes —
a flat ring on O3, a **pod-major** ring on O4 (consecutive hops stay on fast
intra-pod ICI; only one hop per revolution crosses each pod boundary) —
whose one collective is the ``ppermute`` neighbour rotation ring attention
streams K/V panels around.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.core.topology import MeshTopology, topology_of
from repro.obs import trace as obs_trace

__all__ = ["ReducePlan", "reduce_plan", "ambient_plan", "flat_index",
           "RingPlan", "ring_plan", "ambient_ring_plan",
           "CannonPlan", "cannon_plan", "ambient_cannon_plan"]


def _plan_event(kind: str, axes: tuple[str, ...], **attrs) -> None:
    """One trace event per plan execution *trace* (these run inside
    shard_map/jit, so the event fires at trace time — once per
    compilation, not once per device step; attrs are static strings, the
    tracer never sees a jax value)."""
    obs_trace.TRACER.event(f"collectives.{kind}", cat="collectives",
                           axes="x".join(axes) or "-", **attrs)


def _entry(axes: tuple[str, ...]):
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def flat_index(axes: tuple[str, ...], sizes: tuple[int, ...]):
    """This device's flat shard index over ``axes`` (outer-first), inside
    shard_map — e.g. the global row offset of a (pod, data) row shard is
    ``flat_index(('pod', 'data'), (2, 2)) * rows_per_shard``."""
    idx = jnp.int32(0)
    for name, size in zip(axes, sizes):
        idx = idx * size + jax.lax.axis_index(name)
    return idx


@dataclasses.dataclass(frozen=True)
class ReducePlan:
    """A hierarchical reduction schedule over a mesh's batch-role axes.

    ``data_axes``/``pod_axes`` are in mesh (outer-first) order; execution
    always runs the data (intra-pod) stage first and the pod (inter-pod)
    stage last, so the slow boundary only ever carries already-reduced
    values.  ``mesh`` rides along so shard_map executables can be built
    (and lru-cached) from the plan alone.
    """
    mesh: object                     # jax.sharding.Mesh (hashable)
    topo: MeshTopology
    pod_axes: tuple[str, ...]
    data_axes: tuple[str, ...]

    # -- structure ----------------------------------------------------------

    @property
    def batch_axes(self) -> tuple[str, ...]:
        """All reduction axes, outer-first (pod-major) — the PartitionSpec
        entry order for row shards."""
        return self.pod_axes + self.data_axes

    @property
    def width(self) -> int:
        """Total participants = product of the batch-axis sizes."""
        w = 1
        for a in self.batch_axes:
            w *= self.topo.size(a)
        return w

    @property
    def data_width(self) -> int:
        w = 1
        for a in self.data_axes:
            w *= self.topo.size(a)
        return w

    @property
    def hierarchical(self) -> bool:
        """True when the schedule has a real inter-pod stage."""
        return bool(self.pod_axes) and bool(self.data_axes)

    def spec_entry(self):
        """The PartitionSpec entry sharding a dim over the batch axes
        (None / name / tuple, as P() expects)."""
        return _entry(self.batch_axes)

    def data_spec_entry(self):
        """PartitionSpec entry for a dim sharded over the *data* axes only —
        the layout :meth:`psum_scatter` leaves the scattered dim in."""
        return _entry(self.data_axes)

    def schedule(self, terminal: str = "all_reduce"
                 ) -> tuple[tuple[str, str], ...]:
        """The emitted schedule as (collective, axis) steps, for
        introspection and tests.  ``terminal`` names the data-stage
        collective of :meth:`psum_scatter` ('reduce_scatter') or of
        :meth:`psum` ('all_reduce')."""
        first = "reduce_scatter" if terminal == "reduce_scatter" \
            else "all_reduce"
        steps = [(first, a) for a in self.data_axes]
        steps += [("all_reduce", a) for a in self.pod_axes]
        return tuple(steps)

    # -- execution (call these inside shard_map) ----------------------------

    def psum(self, x):
        """Hierarchical all-reduce: data axes (intra-pod) first, then pod."""
        _plan_event("psum", self.batch_axes,
                    hierarchical=self.hierarchical)
        for a in self.data_axes:
            x = jax.lax.psum(x, a)
        for a in self.pod_axes:
            x = jax.lax.psum(x, a)
        return x

    def psum_scatter(self, x, scatter_dimension: int = 0):
        """Reduce-scatter intra-pod, all-reduce inter-pod.  The result is
        sharded over the data axes along ``scatter_dimension`` and
        replicated over the pod axes (out_specs: data entry only).  Data
        axes scatter outermost-first so the shard layout matches
        ``P((*data_axes,))`` along the scattered dim."""
        _plan_event("psum_scatter", self.batch_axes,
                    hierarchical=self.hierarchical,
                    scatter_dimension=scatter_dimension)
        for a in self.data_axes:
            x = jax.lax.psum_scatter(x, a, scatter_dimension=scatter_dimension,
                                     tiled=True)
        for a in self.pod_axes:
            x = jax.lax.psum(x, a)
        return x

    def all_gather(self, x, axis: int = 0):
        """Reassemble batch-axis row shards: gather intra-pod first (ICI),
        then inter-pod (DCN).  Inverse of sharding by :meth:`spec_entry`."""
        _plan_event("all_gather", self.batch_axes,
                    hierarchical=self.hierarchical)
        for a in reversed(self.data_axes):
            x = jax.lax.all_gather(x, a, axis=axis, tiled=True)
        for a in reversed(self.pod_axes):
            x = jax.lax.all_gather(x, a, axis=axis, tiled=True)
        return x

    def shard_index(self):
        """This device's flat batch-shard index (pod-major), inside
        shard_map."""
        sizes = tuple(self.topo.size(a) for a in self.batch_axes)
        return flat_index(self.batch_axes, sizes)

    def halo(self, x, rows: int):
        """``(lo, hi)``: the ``rows`` rows of the batch-axis row shards just
        before and just after this one's ``x`` -- the last rows of shard
        k - 1 and the first rows of shard k + 1 in the flat pod-major
        order :meth:`spec_entry` shards by, so the pod seam is one hop like
        any other.  Two ``ppermute`` shifts over the batch axes; the end
        shards get zeros where they have no neighbour."""
        _plan_event("halo", self.batch_axes, rows=rows)
        w = self.width
        axis = _entry(self.batch_axes)
        lo = jax.lax.ppermute(x[x.shape[0] - rows:], axis,
                              tuple((i, i + 1) for i in range(w - 1)))
        hi = jax.lax.ppermute(x[:rows], axis,
                              tuple((i + 1, i) for i in range(w - 1)))
        return lo, hi


def reduce_plan(mesh, topo: Optional[MeshTopology] = None) -> ReducePlan:
    """Build the :class:`ReducePlan` for ``mesh`` from its axis roles.

    Degenerate (size-1) axes are dropped from the schedule — a ``(data=8,
    model=1)`` mesh plans a single flat psum over ``data``, exactly PR 2's
    behaviour; only a real pod axis buys the hierarchical form."""
    topo = topo if topo is not None else topology_of(mesh)
    if topo is None:
        raise ValueError("reduce_plan needs a mesh (got None)")
    pod = tuple(a for a in topo.axes("pod") if topo.size(a) > 1)
    data = tuple(a for a in topo.axes("data") if topo.size(a) > 1)
    if not data and pod:
        # all batch parallelism lives on pod axes: the intra-pod stage is
        # empty and the pod stage is the whole (flat) reduction
        pod, data = (), pod
    return ReducePlan(mesh=mesh, topo=topo, pod_axes=pod, data_axes=data)


def ambient_plan() -> Optional[ReducePlan]:
    """The plan for the ambient O3/O4 mesh, or None outside one (or when
    the mesh has no batch-role parallelism to reduce over)."""
    ctx = registry.select_context()
    if ctx.scope != "mesh" or ctx.topology is None:
        return None
    plan = reduce_plan(ctx.mesh, ctx.topology)
    return plan if plan.batch_axes else None


# ---------------------------------------------------------------------------
# ring schedules (the sequence-parallel plane, DESIGN.md §10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RingPlan:
    """A neighbour-rotation schedule over a mesh's batch-role axes — the
    collective shape of sequence-parallel (ring) attention.

    ``axes`` are in mesh (outer-first, pod-major) order, so on an O4
    ``(pod, data, model)`` mesh the ring walks all data shards of pod 0,
    then pod 1, ...: ``size - n_pods`` of the hops are fast intra-pod ICI
    neighbour exchanges and only the pod-seam hops cross the DCN.  On an O3
    mesh the ring is flat over ``data``.  Frozen/hashable so shard_map
    executables cache per plan, exactly like :class:`ReducePlan`.
    """
    mesh: object                     # jax.sharding.Mesh (hashable)
    topo: MeshTopology
    axes: tuple[str, ...]            # pod-major ring axes

    @property
    def size(self) -> int:
        """Ring participants = product of the ring-axis sizes."""
        w = 1
        for a in self.axes:
            w *= self.topo.size(a)
        return w

    def spec_entry(self):
        """The PartitionSpec entry sharding the sequence dim over the ring
        (None / name / tuple, as P() expects)."""
        return _entry(self.axes)

    @property
    def perm(self) -> tuple[tuple[int, int], ...]:
        """One rotation hop: shard ``i`` sends its K/V panel to ``i + 1``
        (mod size), so after ``h`` hops shard ``r`` holds the panel that
        started on shard ``(r - h) mod size``."""
        w = self.size
        return tuple((i, (i + 1) % w) for i in range(w))

    def schedule(self) -> tuple[tuple[str, tuple[str, ...]], ...]:
        """The emitted schedule as (collective, axes) steps — one
        ``ppermute`` rotation per non-self hop — for introspection/tests."""
        return (("ppermute", self.axes),) * (self.size - 1)

    # -- execution (call these inside shard_map) ----------------------------

    def shift(self, x):
        """Rotate ``x`` one hop around the ring (pod-major flat order)."""
        _plan_event("ring_shift", self.axes, size=self.size)
        axis = self.axes if len(self.axes) > 1 else self.axes[0]
        return jax.lax.ppermute(x, axis, self.perm)

    def ring_index(self):
        """This device's flat ring position (pod-major), inside shard_map."""
        sizes = tuple(self.topo.size(a) for a in self.axes)
        return flat_index(self.axes, sizes)

    def psum(self, x):
        """All-reduce ``x`` over the ring participants — the rotation
        schedule's reduction dual: where prefill *rotates* K/V panels and
        each shard folds hops locally (§10), paged decode keeps pages
        pinned and *reduces* the per-shard (o·w, w) partials in one step
        (DESIGN.md §13)."""
        _plan_event("ring_psum", self.axes, size=self.size)
        axis = self.axes if len(self.axes) > 1 else self.axes[0]
        return jax.lax.psum(x, axis)

    def pmax(self, x):
        """All-max over the ring participants — the softmax row-max half of
        the decode-side state merge (pairs with :meth:`psum`)."""
        _plan_event("ring_pmax", self.axes, size=self.size)
        axis = self.axes if len(self.axes) > 1 else self.axes[0]
        return jax.lax.pmax(x, axis)


def ring_plan(mesh, topo: Optional[MeshTopology] = None) -> RingPlan:
    """Build the :class:`RingPlan` for ``mesh`` from its axis roles.

    The ring runs over the batch-role (pod × data) axes — the same
    participants :func:`reduce_plan` reduces over — with degenerate (size-1)
    axes dropped; model axes replicate (a head-parallel dimension never
    joins the sequence ring)."""
    topo = topo if topo is not None else topology_of(mesh)
    if topo is None:
        raise ValueError("ring_plan needs a mesh (got None)")
    axes = tuple(a for a in topo.axes("pod", "data") if topo.size(a) > 1)
    return RingPlan(mesh=mesh, topo=topo, axes=axes)


def ambient_ring_plan() -> Optional[RingPlan]:
    """The ring plan for the ambient O3/O4 mesh, or None outside one (or
    when the mesh has no batch-role axis to ring over)."""
    ctx = registry.select_context()
    if ctx.scope != "mesh" or ctx.topology is None:
        return None
    plan = ring_plan(ctx.mesh, ctx.topology)
    return plan if plan.axes else None


# ---------------------------------------------------------------------------
# Cannon schedules (the SpGEMM mesh plane, DESIGN.md §15)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CannonPlan:
    """A Cannon-style 2-D distribution schedule for mesh SpGEMM.

    Classic Cannon lays C's block grid over a ``rows × cols`` process mesh
    and skew-rotates A panels row-wise and B panels column-wise.  On a
    shard_map mesh the two rotations dualize into the collective pair this
    plan emits: every device computes a slice of the *block-product pair
    list* (sharded flat over all participating axes — the skew collapsed
    into the partition), then partials meet C's owners via

        psum           over the col (model) axes — B's column broadcast,
                       reversed: partial products for the same output
                       block-row land on every column rank and fold there
        psum_scatter   over the row (pod × data) axes — A's row broadcast
                       reversed into a reduce-scatter, leaving C's value
                       blocks row-sharded (tiled, dim 0) with only
                       already-reduced tiles crossing the pod seam

    ``row_axes`` are the batch-role (pod-major) axes C's block-rows shard
    over; ``col_axes`` the model-role axes that only ever carry partials.
    Frozen/hashable so shard_map executables cache per plan, exactly like
    :class:`ReducePlan`/:class:`RingPlan`.
    """
    mesh: object                     # jax.sharding.Mesh (hashable)
    topo: MeshTopology
    row_axes: tuple[str, ...]        # pod-major: C's block-row shard axes
    col_axes: tuple[str, ...]        # model-role: partial-product axes

    @property
    def rows(self) -> int:
        """Row ranks = product of the row-axis sizes (C's shard count)."""
        w = 1
        for a in self.row_axes:
            w *= self.topo.size(a)
        return w

    @property
    def cols(self) -> int:
        """Column ranks = product of the col-axis sizes."""
        w = 1
        for a in self.col_axes:
            w *= self.topo.size(a)
        return w

    @property
    def size(self) -> int:
        """Total participants = rows × cols (the pair-list shard count)."""
        return self.rows * self.cols

    @property
    def all_axes(self) -> tuple[str, ...]:
        """Every participating axis, row-major then col — the flat
        pair-list partition order."""
        return self.row_axes + self.col_axes

    def row_spec_entry(self):
        """PartitionSpec entry sharding a dim over the row axes — the
        layout :meth:`reduce_partials` leaves C's values in."""
        return _entry(self.row_axes)

    def pair_spec_entry(self):
        """PartitionSpec entry sharding the pair list over *all* axes."""
        return _entry(self.all_axes)

    def schedule(self) -> tuple[tuple[str, str], ...]:
        """The emitted schedule as (collective, axis) steps — col-axis
        all-reduces first, then row-axis reduce-scatters — for
        introspection and tests."""
        steps = [("all_reduce", a) for a in self.col_axes]
        steps += [("reduce_scatter", a) for a in self.row_axes]
        return tuple(steps)

    # -- execution (call these inside shard_map) ----------------------------

    def reduce_partials(self, x, scatter_dimension: int = 0):
        """Fold the per-device partial block products into row-sharded C
        values: psum over the col axes, then tiled reduce-scatter over the
        row axes (outermost-first, so the shard layout matches
        ``P(row_spec_entry())`` along ``scatter_dimension``)."""
        _plan_event("cannon_reduce", self.all_axes,
                    rows=self.rows, cols=self.cols)
        for a in self.col_axes:
            x = jax.lax.psum(x, a)
        for a in self.row_axes:
            x = jax.lax.psum_scatter(x, a, scatter_dimension=scatter_dimension,
                                     tiled=True)
        return x

    def pair_index(self):
        """This device's flat pair-list shard index (row-major), inside
        shard_map."""
        sizes = tuple(self.topo.size(a) for a in self.all_axes)
        return flat_index(self.all_axes, sizes)


def cannon_plan(mesh, topo: Optional[MeshTopology] = None) -> CannonPlan:
    """Build the :class:`CannonPlan` for ``mesh`` from its axis roles:
    batch-role (pod × data) axes become the row dimension, model-role axes
    the column dimension, degenerate (size-1) axes dropped.  A ``(data=8,
    model=1)`` mesh plans an 8×1 distribution (flat reduce-scatter, no
    column stage); ``(pod=2, data=2, model=2)`` plans 4×2."""
    topo = topo if topo is not None else topology_of(mesh)
    if topo is None:
        raise ValueError("cannon_plan needs a mesh (got None)")
    rows = tuple(a for a in topo.axes("pod", "data") if topo.size(a) > 1)
    cols = tuple(a for a in topo.axes("model") if topo.size(a) > 1)
    return CannonPlan(mesh=mesh, topo=topo, row_axes=rows, col_axes=cols)


def ambient_cannon_plan() -> Optional[CannonPlan]:
    """The Cannon plan for the ambient O3/O4 mesh, or None outside one (or
    when the mesh has no batch-role axis to row-shard over — a model-only
    mesh degrades SpGEMM to the chip formulation)."""
    ctx = registry.select_context()
    if ctx.scope != "mesh" or ctx.topology is None:
        return None
    plan = cannon_plan(ctx.mesh, ctx.topology)
    return plan if plan.row_axes else None
