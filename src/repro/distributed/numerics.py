"""Mesh-scoped formulations of the paper's four kernels (DESIGN.md §7-§8).

The paper scales one unchanged program text across cores with
``ARBB_NUM_CORES`` (§3, O2 → O3) but stops at the shared-memory ceiling
(§4: "ArBB is limited to shared memory systems").  This module is the rung
past it: for each paper kernel — mod2am matmul, mod2as SpMV, mod2f FFT and
the §3.4 CG solve — a ``shard_map`` program partitioned over the ambient
mesh registers as a **mesh-scoped registry variant**.  The registry's scope
dimension then selects these automatically whenever an O3/O4 mesh is
ambient and degrades to the chip formulations without one; call sites never
change (the RapidMind lesson: retarget the selection plane, not the source).

Partitioning is **axis-role aware** (DESIGN.md §8): every formulation asks
:func:`repro.distributed.collectives.reduce_plan` for the ambient mesh's
hierarchical reduction schedule instead of hard-coding one axis name.  On an
O3 ``(data, model)`` mesh the plan is the flat single-axis form PR 2
shipped; on an O4 ``(pod, data, model)`` mesh rows shard over pod × data and
every reduction becomes reduce/reduce-scatter intra-pod then all-reduce
inter-pod — the pod axis computes *real* shards instead of replicas.

Partitioning per kernel:

    solver_spmv  row partition over the batch axes (pod × data).  The matrix
                 shards by rows (ELL values/cols rows; DIA diagonal columns;
                 CSR row-pointer sections with values/indices replicated)
                 and each device runs the *chip* formulation on its rows —
                 local kernel dispatch inside ``shard_map``.  DIA takes x by
                 rows and exchanges a halo of max|offset| rows with each
                 neighbouring shard (``ReducePlan.halo``); ELL and CSR take
                 x whole.
    matmul       ``mesh_psum``: K partition over the batch axes; each device
                 computes a full local MXU product and the partials
                 reduce-scatter intra-pod + all-reduce inter-pod into a
                 row-sharded C.  ``mesh_psum_2d`` additionally tiles N over
                 the model axis — the 2-D (data, model) block layout that
                 takes mod2am past a single axis (rank-≥2 meshes only).
    fft          transpose (four-step) algorithm: view n = n1·n2 with
                 n1 = the *data subgrid* width, row-local FFTs of length n2,
                 twiddle scaling (plan-cached, not recomputed per call), an
                 ``all_to_all`` corner turn **within the data subgrid only**
                 (the turn never crosses the slow pod boundary), then column
                 FFTs of length n1.
    cg           the whole O3/O4 solve runs inside one ``shard_map``, the
                 solver's own CG loop with this module's exchange, shard
                 SpMV and psum: vectors live row-sharded over pod × data;
                 each iteration the DIA SpMV exchanges its halo of ``p``
                 with the neighbouring shards (ELL and CSR gather ``p``
                 hierarchically, intra-pod then inter-pod), and every dot
                 product is a local partial pushed through the plan's
                 hierarchical psum — see :func:`cg_mesh`, consumed by
                 ``repro.numerics.solvers``.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import registry
from repro.core.blocking import round_up
from repro.core.containers import Dense, unwrap, wrap
from repro.kernels import ref
from repro.core.topology import topology_of
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.distributed.collectives import (CannonPlan, ReducePlan, _entry,
                                           ambient_cannon_plan, ambient_plan,
                                           cannon_plan, reduce_plan)
from repro.numerics.sparse import CSR, DIA, ELL
from repro.sparse.formats import BSR

from repro.numerics.spmv import csr_row_reduce, dia_panel

__all__ = ["cg_mesh", "mesh_matmul", "mesh_matmul_2d", "mesh_fft",
           "mesh_spmv", "mesh_spmm", "mesh_spgemm", "MESH_SPMV_VARIANTS",
           "data_size", "block_cyclic_perm"]

#: The mesh-scoped solver_spmv variant names, keyed by layout.
MESH_SPMV_VARIANTS = {CSR: "mesh_csr", ELL: "mesh_ell", DIA: "mesh_dia"}


def data_size(mesh) -> int:
    """How many row shards the batch (pod × data) subgrid partitions into
    (0 when the mesh has no batch-role axis) — kept in terms of the plan
    layer so it can never disagree with what the formulations actually do."""
    plan = _plan_for_mesh(mesh)
    return plan.width if plan is not None else 0


def _plan_for_mesh(mesh) -> Optional[ReducePlan]:
    topo = topology_of(mesh)
    if topo is None:
        return None
    plan = reduce_plan(mesh, topo)
    return plan if plan.batch_axes else None


def _require_plan() -> ReducePlan:
    plan = ambient_plan()
    if plan is None:
        raise RuntimeError(
            "mesh-scoped variant invoked without an ambient O3/O4 mesh "
            "carrying a batch-role (pod/data) axis; enter use_level(O3) first")
    return plan


def _mesh_available(ctx: registry.SelectContext) -> bool:
    return (ctx.topology is not None and
            bool(reduce_plan(ctx.mesh, ctx.topology).batch_axes))


# ---------------------------------------------------------------------------
# row-partitioned SpMV: matrix shards per layout, x by rows with a halo (DIA)
# or whole (ELL, CSR), chip kernel dispatched per shard
# ---------------------------------------------------------------------------
#
# Every mesh entry point below splits into a per-call part (pull the shard
# arrays off the operand) and an executable built once per
# (plan, layout signature) via lru_cache and wrapped in jax.jit — so
# repeated dispatches hit the compilation cache exactly like the chip
# kernels' module-level jit wrappers do, instead of retracing a fresh
# shard_map closure per call.  Plans are frozen/hashable, so they key the
# caches the way the bare mesh did in PR 2.

def _spmv_specs(entry) -> dict:
    """shard_map in_specs per layout's shard arrays (x's is prepended)."""
    return {
        "ell": (P(entry, None), P(entry, None)),      # values, cols by rows
        "csr": (P(entry), P(entry), P(), P()),        # rowpi, rowpj; rest whole
        "dia": (P(None, entry),),                     # diag columns by rows
    }


def _spmv_parts(a) -> tuple[str, Any, tuple]:
    """(kind, static signature, shard arrays) for matrix ``a``."""
    if isinstance(a, ELL):
        return "ell", None, (a.values, a.cols)
    if isinstance(a, CSR):
        return "csr", None, (a.rowp[:-1], a.rowp[1:], a.matvals, a.indx)
    if isinstance(a, DIA):
        return "dia", a.offsets, (a.diags,)
    raise TypeError(f"no row partitioning for matrix type {type(a)!r}")


def _max_offset(offsets) -> int:
    return max((abs(o) for o in offsets), default=0)


def _exchange(kind: str, static, plan: ReducePlan):
    """``exchange(x_loc) -> what this shard's SpMV reads of x``, run *inside*
    shard_map on the shard's own rows of x.

    DIA reads max|offset| rows past each end of its rows: ``(x_loc, (lo,
    hi))`` through the plan's halo exchange, two ``ppermute``s of
    max|offset| rows.  ELL and CSR may read any column: the whole x,
    all-gathered through the plan."""
    if kind != "dia":
        return plan.all_gather
    rows = _max_offset(static)

    def exchange(x):
        obs_metrics.METRICS.gauge(
            "distributed.mesh_dia.exchange_bytes_per_iter").set(
                2 * rows * x.dtype.itemsize)
        return x, plan.halo(x, rows)
    return exchange


def _local_spmv(kind: str, static, dot: bool = False):
    """``local(loc, x_in) -> local y rows``, run *inside* shard_map, with
    ``x_in`` what :func:`_exchange` returns (for DIA) or the whole x.

    The shard is handed to the matching chip formulation through the
    registry -- the same program text, one shard at a time: ELL as a
    re-wrapped container, DIA as the ``spmv_dia`` op on the shard's rows
    plus its halo.  With ``dot`` it returns ``(y, x . y)`` over the shard's
    own rows where the formulation takes the dot with y (DIA, in the same
    op) and ``(y, None)`` otherwise."""
    if kind == "dia":
        offsets = static

        def local(loc, x_in):
            (diags,) = loc                  # (ndiags, n_local)
            x, halo = x_in
            if not dot:
                return ops.spmv_dia(diags, offsets, x, halo=halo)
            with ops.spmv_dia_dots() as dots:
                y = ops.spmv_dia(diags, offsets, x, halo=halo)
            return y, dots[0]
        return local

    if kind == "ell":
        def local(loc, xf):
            vals, cols = loc
            shard = ELL(values=vals, cols=cols,
                        shape=(vals.shape[0], xf.shape[0]))
            return unwrap(registry.dispatch("solver_spmv", shard, wrap(xf),
                                            variant="ell"))
    else:                                           # "csr"
        def local(loc, xf):
            rowpi, rowpj, matvals, indx = loc
            # the paper's map(local::reduce) over this device's row sections
            return jax.vmap(csr_row_reduce(matvals, indx, xf))(rowpi, rowpj)
    return (lambda loc, xf: (local(loc, xf), None)) if dot else local


@functools.lru_cache(maxsize=None)
def _spmv_exec(plan: ReducePlan, kind: str, static):
    local_fn = _local_spmv(kind, static)
    entry = plan.spec_entry()
    if kind == "dia":               # x by rows, the halo exchanged
        exchange = _exchange(kind, static, plan)
        x_spec = P(entry)
    else:                           # x whole on every shard
        exchange, x_spec = (lambda x: x), P()

    def run(x, *loc):
        return local_fn(loc, exchange(x))

    return jax.jit(jax.shard_map(run, mesh=plan.mesh,
                                 in_specs=(x_spec,) + _spmv_specs(entry)[kind],
                                 out_specs=P(entry), check_vma=False))


def mesh_spmv(a, invec, **_: Any) -> Dense:
    """Row-partitioned SpMV over the ambient mesh (y sharded by rows)."""
    plan = _require_plan()
    kind, static, arrays = _spmv_parts(a)
    y = _spmv_exec(plan, kind, static)(unwrap(wrap(invec)), *arrays)
    return wrap(y)


def _spmv_accepts(layout):
    def accepts(m, v, **_):
        plan = ambient_plan()
        # 1-D x only: a 2-D multi-RHS x belongs to the spmm plane (the
        # solver_spmv 'spmm' route), whose mesh variant shards the same way
        if not (isinstance(m, layout) and
                getattr(unwrap(v), "ndim", 1) == 1 and plan is not None and
                m.shape[0] % plan.width == 0):
            return False
        # a DIA shard's halo comes from its two neighbours alone
        return (layout is not DIA or
                m.shape[0] // plan.width >= _max_offset(m.offsets))
    return accepts


# costs mirror the chip ordering (dia < ell < csr) — irrelevant against chip
# variants (scope ranks first) but meaningful among the mesh formulations.
registry.register("solver_spmv", "mesh_dia", mesh_spmv, scope="mesh",
                  cost=4.0, available=_mesh_available,
                  accepts=_spmv_accepts(DIA),
                  doc="row-sharded DIA over pod x data: halo exchange, "
                      "chip spmv_dia per shard")
registry.register("solver_spmv", "mesh_ell", mesh_spmv, scope="mesh",
                  cost=8.0, available=_mesh_available,
                  accepts=_spmv_accepts(ELL),
                  doc="row-sharded ELL; chip kernel dispatched per shard")
registry.register("solver_spmv", "mesh_csr", mesh_spmv, scope="mesh",
                  cost=15.0, available=_mesh_available,
                  accepts=_spmv_accepts(CSR),
                  doc="row-pointer sections sharded; per-row recorded _for")


# ---------------------------------------------------------------------------
# row-partitioned SpMM (the blocked-sparse plane, DESIGN.md §9): same row
# sharding as mesh_spmv, X panel replicated, panel-widened local kernels
# ---------------------------------------------------------------------------

def _local_spmm(kind: str, static, plan: ReducePlan):
    """``local(loc, x_panel) -> local y rows (rows_local, k)`` — the SpMM
    dual of :func:`_local_spmv`: each device's rows of A multiply the whole
    replicated (n, k) RHS panel."""
    if kind == "ell":
        def local(loc, xf):
            vals, cols = loc
            return ref.spmm_ell_ref(vals, cols, xf)     # row-gather × panel
        return local

    if kind == "csr":
        def local(loc, xf):
            rowpi, rowpj, matvals, indx = loc

            def reduce(ri, rj):
                def body(i, acc):
                    return acc + matvals[i] * xf[indx[i], :]
                return jax.lax.fori_loop(
                    ri, rj, body, jnp.zeros((xf.shape[1],), matvals.dtype))
            return jax.vmap(reduce)(rowpi, rowpj)
        return local

    offsets = static                                # "dia"

    def local(loc, xf):
        (diags,) = loc                      # (ndiags, n_local)
        row0 = plan.shard_index() * diags.shape[1]
        return dia_panel(diags, offsets, xf, row0=row0)
    return local


@functools.lru_cache(maxsize=None)
def _spmm_exec(plan: ReducePlan, kind: str, static):
    local_fn = _local_spmm(kind, static, plan)
    entry = plan.spec_entry()

    def run(xf, *loc):
        return local_fn(loc, xf)

    return jax.jit(jax.shard_map(run, mesh=plan.mesh,
                                 in_specs=(P(),) + _spmv_specs(entry)[kind],
                                 out_specs=P(entry, None), check_vma=False))


def mesh_spmm(a, x, **_: Any) -> Dense:
    """Row-partitioned SpMM over the ambient mesh: the matrix shards by
    rows over pod × data exactly as :func:`mesh_spmv`, the (n, k) RHS panel
    replicates, and each device runs the panel-widened local formulation on
    its rows — Y comes back row-sharded.  BSR stays a chip formulation
    (its per-block-row raggedness has no even row shard in general), so a
    blocked operand degrades gracefully under a mesh."""
    plan = _require_plan()
    kind, static, arrays = _spmv_parts(a)
    y = _spmm_exec(plan, kind, static)(unwrap(wrap(x)), *arrays)
    return wrap(y)


def _spmm_accepts(m, v, **_):
    plan = ambient_plan()
    return (isinstance(m, (CSR, ELL, DIA)) and
            getattr(unwrap(v), "ndim", 0) == 2 and plan is not None and
            m.shape[0] % plan.width == 0)


registry.register("spmm", "mesh_spmm", mesh_spmm, scope="mesh", cost=1.0,
                  available=_mesh_available, accepts=_spmm_accepts,
                  doc="row-sharded SpMM over pod x data; RHS panel "
                      "replicated (CSR/ELL/DIA; BSR stays chip)")


# ---------------------------------------------------------------------------
# Cannon-style mesh SpGEMM (the blocked plane's sparse × sparse, DESIGN.md
# §15): pair list sharded over ALL mesh axes, partials folded by a
# CannonPlan, the product returned block-row-sharded — with the decided
# output layout propagated through dispatch (Variant.out_sharding)
# ---------------------------------------------------------------------------

def _require_cannon_plan() -> CannonPlan:
    plan = ambient_cannon_plan()
    if plan is None:
        raise RuntimeError(
            "mesh_spgemm invoked without an ambient O3/O4 mesh carrying a "
            "batch-role (pod/data) axis; enter use_level(O3) first")
    return plan


def _cannon_available(ctx: registry.SelectContext) -> bool:
    return (ctx.topology is not None and
            bool(cannon_plan(ctx.mesh, ctx.topology).row_axes))


@functools.lru_cache(maxsize=None)
def _spgemm_exec(plan: CannonPlan, ncpad: int):
    """One executable per (plan, padded output length): each device runs
    the pair formulation on its pair-list shard (einsum over its gathered
    block pairs, segment-sum into a full-length f32 partial), then the
    plan's psum-cols + reduce-scatter-rows fold leaves C's value blocks
    row-sharded.  Operand values replicate — the pair *list* carries the
    2-D distribution (the Cannon skew collapsed into the partition)."""
    pair_entry = plan.pair_spec_entry()
    row_entry = plan.row_spec_entry()

    def local(av, bv, pp, pq, pr):
        prod = jnp.einsum("pij,pjk->pik", av[pp].astype(jnp.float32),
                          bv[pq].astype(jnp.float32))
        part = jax.ops.segment_sum(prod, pr, num_segments=ncpad)
        return plan.reduce_partials(part, scatter_dimension=0) \
            .astype(av.dtype)

    return jax.jit(jax.shard_map(
        local, mesh=plan.mesh,
        in_specs=(P(), P(), P(pair_entry), P(pair_entry), P(pair_entry)),
        out_specs=P(row_entry, None, None), check_vma=False))


def mesh_spgemm(a, b, **_: Any):
    """C = A·B over the ambient mesh, Cannon-style (DESIGN.md §15).

    The symbolic phase runs on host exactly as on chip; the pair list then
    shards flat over every participating axis (padded to a multiple of the
    plan size with pairs pointing at an appended all-zero A block — slot-0
    contributions of exact zero), and the per-device partials meet C's
    owners through the plan's hierarchical fold.  C's value blocks come
    back sharded ``P(row_axes)`` with ``len`` padded to a multiple of the
    row width; the pad blocks hold zeros and ``rowp`` never references
    them, so every downstream consumer (todense, chained spmm) sees the
    exact product.  The dispatcher attaches the decided layout to the
    result (``C.out_sharding``), so a chained mesh op consumes the product
    without a reshard."""
    from repro.sparse.spgemm import spgemm_symbolic

    plan = _require_cannon_plan()
    sym = spgemm_symbolic(a, b)
    bs = a.block
    nc = sym.nc
    if nc == 0 or sym.npairs == 0:
        return BSR(values=jnp.zeros((nc, bs, bs), a.values.dtype),
                   cols=jnp.asarray(sym.c_cols),
                   rowp=jnp.asarray(sym.c_rowp),
                   shape=(a.shape[0], b.shape[1]), block=bs)
    ncpad = round_up(nc, plan.rows)
    npad = round_up(sym.npairs, plan.size)
    fill = npad - sym.npairs
    pp = np.concatenate([sym.pair_p,
                         np.full(fill, a.values.shape[0], np.int32)])
    pq = np.concatenate([sym.pair_q, np.zeros(fill, np.int32)])
    pr = np.concatenate([sym.pair_r, np.zeros(fill, np.int32)])
    av = jnp.concatenate([a.values, jnp.zeros((1, bs, bs), a.values.dtype)])
    vals = _spgemm_exec(plan, ncpad)(av, b.values, jnp.asarray(pp),
                                     jnp.asarray(pq), jnp.asarray(pr))
    cols = np.concatenate([np.asarray(sym.c_cols),
                           np.zeros(ncpad - nc, np.int32)])
    return BSR(values=vals, cols=jnp.asarray(cols),
               rowp=jnp.asarray(sym.c_rowp),
               shape=(a.shape[0], b.shape[1]), block=bs)


def _spgemm_mesh_accepts(a, b, **_):
    plan = ambient_cannon_plan()
    return (plan is not None and isinstance(a, BSR) and isinstance(b, BSR)
            and a.block == b.block and a.shape[1] == b.shape[0]
            and a.shape[0] % (plan.rows * a.block) == 0)


def _spgemm_out_sharding(ctx: registry.SelectContext, a, b, **_):
    """The layout mesh_spgemm actually leaves C.values in: block-sharded
    over the plan's row axes — what shard_map's out_specs produce, declared
    so dispatch can hand it to the consumer (and explain can show it)."""
    plan = ambient_cannon_plan()
    if plan is None:
        return None
    # no trailing Nones: jax normalises realised output specs that way, so
    # the declaration compares == to C.values.sharding, not just equivalent
    return NamedSharding(plan.mesh, P(plan.row_spec_entry()))


registry.register("spgemm", "mesh_spgemm", mesh_spgemm, scope="mesh",
                  cost=1.0, available=_cannon_available,
                  accepts=_spgemm_mesh_accepts,
                  out_sharding=_spgemm_out_sharding,
                  doc="Cannon-style pair partition over pod x data (x "
                      "model): psum cols + reduce-scatter rows; product "
                      "returned block-row-sharded")


# ---------------------------------------------------------------------------
# K-partitioned matmul: local MXU tiles + a hierarchical reduction plan
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _matmul_exec(plan: ReducePlan, plane: str, blocks):
    block_m, block_n, block_k = blocks
    kentry = plan.spec_entry()

    def local(al, bl):
        part = registry.dispatch("matmul", al, bl, variant=plane,
                                 block_m=block_m, block_n=block_n,
                                 block_k=block_k)
        return plan.psum_scatter(part, scatter_dimension=0)

    return jax.jit(jax.shard_map(local, mesh=plan.mesh,
                                 in_specs=(P(None, kentry), P(kentry, None)),
                                 out_specs=P(plan.data_spec_entry(), None),
                                 check_vma=False))


def mesh_matmul(a, b, *, block_m=None, block_n=None, block_k=None):
    """C = A @ B with A column- and B row-sharded along K (pod × data).

    Each device multiplies its K panel with the chip kernel (pallas on TPU,
    xla elsewhere — the plane resolves exactly as on one chip), then the
    full-size partials run the plan's hierarchical reduction: reduce-scatter
    intra-pod, all-reduce inter-pod.  C comes back row-sharded over the data
    axes (replicated across pods); no device ever holds more than
    (M, K/D) + (K/D, N) + (M, N) floats.
    """
    plan = _require_plan()
    plane = registry.resolve_backend()      # chip variant names == planes
    fn = _matmul_exec(plan, plane, (block_m, block_n, block_k))
    return fn(unwrap(wrap(a)), unwrap(wrap(b)))


def _matmul_accepts(a, b, **_):
    plan = ambient_plan()
    return (plan is not None and getattr(a, "ndim", 0) == 2 and
            getattr(b, "ndim", 0) == 2 and
            a.shape[0] % plan.data_width == 0 and
            a.shape[1] % plan.width == 0)


registry.register("matmul", "mesh_psum", mesh_matmul, scope="mesh", cost=1.0,
                  available=_mesh_available, accepts=_matmul_accepts,
                  doc="K-partitioned shard_map matmul, hierarchical "
                      "reduce-scatter/all-reduce along K")


@functools.lru_cache(maxsize=None)
def _matmul2d_exec(plan: ReducePlan, model_axes: tuple, plane: str, blocks):
    block_m, block_n, block_k = blocks
    kentry = plan.spec_entry()
    mentry = _entry(model_axes)

    def local(al, bl):
        part = registry.dispatch("matmul", al, bl, variant=plane,
                                 block_m=block_m, block_n=block_n,
                                 block_k=block_k)
        return plan.psum_scatter(part, scatter_dimension=0)

    return jax.jit(jax.shard_map(local, mesh=plan.mesh,
                                 in_specs=(P(None, kentry), P(kentry, mentry)),
                                 out_specs=P(plan.data_spec_entry(), mentry),
                                 check_vma=False))


def _model_axes(plan: ReducePlan) -> tuple:
    return tuple(a for a in plan.topo.axes("model") if plan.topo.size(a) > 1)


#: Column-panel unit for the block-cyclic N assignment: one MXU tile.
N_PANEL = 128


@functools.lru_cache(maxsize=None)
def block_cyclic_perm(n: int, t: int, panel: int = N_PANEL):
    """Block-cyclic column assignment of N panels across ``t`` model tiles.

    Returns ``(perm, inv)`` such that after ``b[:, perm]`` the *contiguous*
    model sharding P(..., model) hands shard ``s`` the panels ``s, s+t,
    s+2t, ...`` — panels deal out round-robin instead of in one contiguous
    run, so a tall-skinny N (many panels) spreads its leading/trailing
    structure across the model axis instead of loading it onto one shard
    (the DBCSR 2-D block-cyclic lesson; ROADMAP item).  ``inv`` restores
    global column order on the result.  Returns ``None`` when the cyclic
    layout degenerates to the contiguous one (``n`` doesn't tile into
    ``t × panel`` panels, or exactly one panel per shard)."""
    if t <= 1 or n % (panel * t) != 0 or n // panel == t:
        return None
    npanels = n // panel
    order = np.concatenate([
        np.arange(p * panel, (p + 1) * panel)
        for s in range(t) for p in range(s, npanels, t)])
    inv = np.argsort(order)
    return order, inv


@functools.lru_cache(maxsize=None)
def _matmul2d_cyclic_exec(plan: ReducePlan, model_axes: tuple, plane: str,
                          blocks):
    inner = _matmul2d_exec(plan, model_axes, plane, blocks)

    def run(av, bv, perm, inv):
        return inner(av, bv[:, perm])[:, inv]

    return jax.jit(run)


def mesh_matmul_2d(a, b, *, block_m=None, block_n=None, block_k=None):
    """C = A @ B on the 2-D (data, model) block layout (mod2am past one axis).

    K partitions over the batch axes (pod × data) exactly as
    :func:`mesh_matmul`, and N additionally tiles over the model axis: each
    device multiplies a (M, K/D) × (K/D, N/T) tile, so the local MXU work
    *and* the partials shrink by the model width T.  The K reduction is the
    plan's hierarchical schedule (reduce-scatter intra-pod, all-reduce
    inter-pod), leaving C in the 2-D block layout P(data, model) — rows by
    data shard, columns by model tile, replicated across pods.

    N panels are assigned **block-cyclically** (:func:`block_cyclic_perm`):
    B's columns are dealt out in :data:`N_PANEL`-wide panels round-robin
    across the model tiles, and C's columns gather back to global order —
    both permutations traced inside one jitted executable so XLA fuses
    them with the matmul (on the cyclic path the *returned* C is therefore
    in global column order, not the raw P(data, model) block layout).
    Tall-skinny N no longer load-imbalances rank-≥2 meshes; when N doesn't
    tile into panels the layout degenerates to the contiguous assignment
    unchanged.
    """
    plan = _require_plan()
    plane = registry.resolve_backend()
    av, bv = unwrap(wrap(a)), unwrap(wrap(b))
    t = 1
    for ax in _model_axes(plan):
        t *= plan.topo.size(ax)
    key = (plan, _model_axes(plan), plane, (block_m, block_n, block_k))
    cyclic = block_cyclic_perm(bv.shape[1], t, block_n or N_PANEL)
    if cyclic is None:
        return _matmul2d_exec(*key)(av, bv)
    perm, inv = cyclic
    return _matmul2d_cyclic_exec(*key)(av, bv, perm, inv)


def _matmul2d_available(ctx: registry.SelectContext) -> bool:
    # rank >= 2 with a real model axis: the 2-D tiling needs a second
    # non-degenerate mesh dimension to tile N over
    return (_mesh_available(ctx) and ctx.mesh_rank >= 2 and
            ctx.topology.extent("model") > 1)


def _matmul2d_accepts(a, b, **_):
    plan = ambient_plan()
    if plan is None:
        return False
    t = 1
    for ax in _model_axes(plan):
        t *= plan.topo.size(ax)
    return (t > 1 and getattr(a, "ndim", 0) == 2 and
            getattr(b, "ndim", 0) == 2 and
            a.shape[0] % plan.data_width == 0 and
            a.shape[1] % plan.width == 0 and
            b.shape[1] % t == 0)


registry.register("matmul", "mesh_psum_2d", mesh_matmul_2d, scope="mesh",
                  cost=0.5, available=_matmul2d_available,
                  accepts=_matmul2d_accepts,
                  doc="2-D (data, model) tiling: K over pod x data, N over "
                      "model; hierarchical K reduction")


# ---------------------------------------------------------------------------
# transpose-based distributed FFT (four-step: FFT, twiddle, corner turn, FFT)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fft_twiddles(n: int, n1: int, dtype: str) -> jax.Array:
    """The (n1, n2) twiddle table W_n^{i1·k2} for the corner turn, built
    once per (n, subgrid width, dtype) — the distributed analogue of the
    chip FFT's bit-reversal/twiddle plan cache.  Committed to device so
    repeated solves reuse the same buffer instead of re-exp'ing per call."""
    i1 = np.arange(n1)[:, None]
    k2 = np.arange(n // n1)[None, :]
    return jax.device_put(jnp.asarray(
        np.exp(-2j * np.pi * (i1 * k2) / n), dtype))


@functools.lru_cache(maxsize=None)
def _fft_exec(plan: ReducePlan):
    (turn_axis,) = plan.data_axes       # the corner turn stays intra-pod
    n1 = plan.data_width

    def local(al, twl):                 # (n1/D = 1 row, n2) per data shard
        b = jnp.fft.fft(al, axis=1)
        b = b * twl.astype(b.dtype)
        # corner turn: (rows, n2) row shards -> (n1, n2/D) column shards,
        # all_to_all only within the data subgrid (never across pods)
        bt = jax.lax.all_to_all(b, turn_axis, split_axis=1, concat_axis=0,
                                tiled=True)
        return jnp.fft.fft(bt, axis=0)  # FFT over i1 -> k1

    def full(x, tw):
        n = x.shape[0]
        # A[i1, i2] = x[i1 + n1*i2], row-sharded over the data subgrid
        a = jnp.reshape(x, (n // n1, n1)).T
        c = jax.shard_map(local, mesh=plan.mesh,
                          in_specs=(P(turn_axis, None), P(turn_axis, None)),
                          out_specs=P(None, turn_axis), check_vma=False)(a, tw)
        # X[n2*k1 + k2] = C[k1, k2]: row-major flatten is the output order
        return jnp.reshape(c, (n,)).astype(x.dtype)

    return jax.jit(full)


def mesh_fft(x):
    """Distributed DFT of a length-n vector via the transpose algorithm.

    With i = i1 + n1·i2 and k = k2 + n2·k1 (n1 = data-subgrid width):

        X[n2·k1 + k2] = Σ_{i1} W_{n1}^{i1·k1} · W_n^{i1·k2}
                        · Σ_{i2} W_{n2}^{i2·k2} x[i1 + n1·i2]

    Each data shard owns one i1-row: an n2-point local FFT, the W_n^{i1·k2}
    twiddle scale (from the plan-level twiddle cache), then a single
    ``all_to_all`` corner turn re-shards along k2 so the final n1-point FFTs
    are column-local.  The turn runs only within the data subgrid — pod and
    model axes replicate, so the transpose never pays a DCN hop.  One global
    transpose replaces the per-stage cross-device butterflies — the
    split-stream lesson (keep data movement structural) at mesh scale.
    """
    plan = _require_plan()
    tw = _fft_twiddles(x.shape[0], plan.data_width, str(x.dtype))
    return _fft_exec(plan)(x, tw)


def _fft_accepts(x):
    plan = ambient_plan()
    if plan is None or len(plan.data_axes) != 1:
        return False
    D = plan.data_width
    n = x.shape[0] if getattr(x, "ndim", 0) == 1 else 0
    return (D > 1 and n >= 2 and (n & (n - 1)) == 0 and
            n % D == 0 and (n // D) % D == 0)


registry.register("fft", "mesh_transpose", mesh_fft, scope="mesh", cost=1.0,
                  available=_mesh_available, accepts=_fft_accepts,
                  doc="four-step transpose FFT: local FFTs + one all_to_all "
                      "inside the data subgrid")


# ---------------------------------------------------------------------------
# distributed CG: the whole solve inside one shard_map, every reduction a
# hierarchical plan
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _cg_exec(plan: ReducePlan, kind: str, static, max_iters: int, loop):
    exchange = _exchange(kind, static, plan)
    local_fn = _local_spmv(kind, static, dot=True)
    entry = plan.spec_entry()

    def run(stop, b_loc, *a_loc):
        return loop(b_loc, stop, max_iters,
                    spmv=lambda p_in: local_fn(a_loc, p_in),
                    reduce=plan.psum, exchange=exchange)

    return jax.jit(jax.shard_map(
        run, mesh=plan.mesh,
        in_specs=(P(), P(entry)) + _spmv_specs(entry)[kind],
        out_specs=(P(entry), P(), P()), check_vma=False))


def cg_mesh(a, bv: jax.Array, *, stop, max_iters: int, loop, mesh=None,
            variant: Any = None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The paper's §3.4 CG iteration, row-sharded end-to-end: ``loop``,
    the solver's own CG loop (``solvers._cg_loop``), run inside one
    shard_map with this scope's exchange, shard SpMV and psum.

    Vectors (x, r, p) live as row shards over the batch axes (pod × data on
    O4).  Each iteration gives the local SpMV what it reads of ``p`` from
    other shards -- for DIA the max|offset| rows past each end of the shard
    (the plan's halo exchange), for ELL and CSR the whole of ``p`` (the
    plan's hierarchical all-gather) -- and pushes the two dot products
    through the plan's hierarchical psum (intra-pod reduce, then one
    already-reduced scalar across the pod boundary): the only cross-device
    traffic.  For DIA the shard's kernel returns its part of p.Ap with Ap,
    as on one chip.  Loop control (r2, k) is psum-replicated, so every
    device takes the same branch.  Returns the same (x, r2, k) triple as
    the chip core, with x row-sharded over the mesh.

    ``variant`` is the caller's explicit solver_spmv pin, if any: the
    partitioning is determined by the operand layout, so a pin that names a
    different mesh formulation is an error, not a silent substitution.
    """
    plan = _plan_for_mesh(mesh) if mesh is not None else _require_plan()
    if plan is None:
        raise RuntimeError(f"mesh {mesh} has no batch-role axis to shard over")
    expected = MESH_SPMV_VARIANTS[type(a)]
    if variant is not None and variant != expected:
        raise ValueError(
            f"solver_spmv variant {variant!r} was pinned, but a "
            f"{type(a).__name__} operand row-partitions as {expected!r}")
    kind, static, arrays = _spmv_parts(a)
    stop = jnp.asarray(stop, bv.dtype)
    return _cg_exec(plan, kind, static, int(max_iters), loop)(stop, bv,
                                                              *arrays)
