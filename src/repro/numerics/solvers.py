"""Linear solvers: conjugate gradients (paper §3.4), Jacobi & Gauss-Seidel
(ported to ArBB per the paper's introduction).

The CG port is the paper's listing, line for line, on the DSL: the iteration
is a recorded ``_while`` whose condition is ``r2 > stop && k < max_iters`` and
whose body composes the SpMV kernel with ``add_reduce`` dot products.  The
SpMV formulation is a registry variant (``solver_spmv`` in
:mod:`repro.core.registry`) — the paper runs arbb_spmv1/arbb_spmv2; we add
the TPU-native DIA path for the banded Table-2 systems (gather-free;
DESIGN.md §2).  ``backend=None`` auto-selects the strongest formulation the
matrix layout admits.

``cg_solve`` keeps the whole iteration on device: the returned
:class:`CGResult` carries device scalars for the iteration count and final
residual, so composing solves (or jitting around them) never forces a host
sync — convert with ``int()`` / ``float()`` at the edge where a Python value
is genuinely needed.

The solve is also **scope-aware** (DESIGN.md §7-§8): under ``use_level(O3)``
with an ambient mesh the registry selects a mesh-scoped ``solver_spmv``
variant, and the same iteration runs inside the shard_map of
:func:`repro.distributed.numerics.cg_mesh` — vectors row-sharded over the
batch axes, SpMV local per shard, both dot products pushed through the
mesh's hierarchical reduction plan (on an O4 ``(pod, data, model)`` mesh:
reduce intra-pod over ``data``, then one already-reduced scalar across the
``pod`` boundary).  Same program text at the call site; ``ARBB_NUM_CORES``
reborn as mesh shape.  An explicit ``backend=`` still pins either
formulation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import Dense, add_reduce, arbb_while, call, unwrap, wrap
from repro.core import registry
from repro.numerics import spmv as spmv_mod  # noqa: F401  (registers solver_spmv)
from repro.numerics.sparse import CSR, DIA, ELL

__all__ = ["cg_solve", "cg_block_solve", "jacobi_solve",
           "gauss_seidel_solve", "CGResult", "BlockCGResult"]

Matrix = Union[CSR, ELL, DIA]


@dataclasses.dataclass
class CGResult:
    """Device-resident result; ``int(res.iterations)`` / ``float(res.
    residual_sq)`` sync at the caller's edge, not inside the solver."""
    x: Dense
    iterations: jax.Array       # int32 scalar, on device
    residual_sq: jax.Array      # f32 scalar, on device


def _selected_spmv(a: Matrix, bv, backend: Optional[str]) -> registry.Variant:
    """The solver_spmv variant the registry would run for this solve —
    the scope decision (chip loop vs mesh shard_map) hangs off its scope."""
    return registry.select("solver_spmv", a, wrap(bv), variant=backend)


def cg_solve(a: Matrix, b, *, stop: float = 1e-10, max_iters: int = 1000,
             backend: Optional[str] = None) -> CGResult:
    """Conjugate gradients, the paper's §3.4 listing on the DSL.

    Initialisation per the paper (x0 = 0, r0 = b, p0 = b - A x0 = b).
    ``backend`` names a ``solver_spmv`` registry variant ('spmv1', 'spmv2',
    'ell', 'dia', or the mesh-scoped 'mesh_*' forms); None lets the registry
    pick by matrix layout *and* scope — under an active O3/O4 mesh the whole
    solve runs sharded, with every dot product a hierarchical reduction plan
    (intra-pod first, pod boundary last)."""
    x, r2, k = _cg_jit_core(a, unwrap(wrap(b)), stop, max_iters, backend)
    return CGResult(x=wrap(x), iterations=k, residual_sq=r2)


def _cg_loop(bv, stop, max_iters: int, *, spmv, reduce=lambda s: s,
             exchange=lambda p: p):
    """The CG iteration from x0 = 0, r0 = p0 = b; returns (x, r2, k).

    The one loop of both scopes; each hands in what differs.
    ``exchange(p)`` gives what the SpMV reads of p (p on one chip; the
    halo or the whole of p on the mesh); ``spmv(p_in)`` returns ``(Ap,
    p.Ap)`` where the formulation takes the dot (DIA's kernel, while both
    are on chip) and ``(Ap, None)`` otherwise; ``reduce`` sums a partial
    dot over the shards (the plan's psum on the mesh).

    The body's steps carry ``jax.named_scope``s -- ``cg.exchange``,
    ``cg.spmv``, ``cg.dot``, ``cg.update`` -- which reach the optimised
    HLO's ``op_name`` metadata, so a profiler trace names each fusion by
    the CG step it computes."""
    def cond(state):
        x, r, p, r2, k = state
        return jnp.logical_and(r2 > stop, k < max_iters)

    def body(state):
        x, r, p, r2, k = state
        with jax.named_scope("cg.exchange"):
            p_in = exchange(p)
        with jax.named_scope("cg.spmv"):
            ap, pap = spmv(p_in)                    # Ap = A @ p
        with jax.named_scope("cg.dot"):
            if pap is None:
                pap = jnp.sum(p * ap)
            alpha = r2 / reduce(pap)
        with jax.named_scope("cg.update"):
            r_new = r - alpha * ap
        with jax.named_scope("cg.dot"):
            r2_new = reduce(jnp.sum(r_new * r_new))
        with jax.named_scope("cg.update"):
            beta = r2_new / r2
            x_new = x + alpha * p
            p_new = r_new + beta * p
        return (x_new, r_new, p_new, r2_new, k + 1)

    init = (jnp.zeros_like(bv), bv, bv, reduce(jnp.sum(bv * bv)),
            jnp.int32(0))
    x, r, p, r2, k = arbb_while(cond, body, init)
    return x, r2, k


def _cg_jit_core(a: Matrix, bv, stop, max_iters: int, backend: Optional[str]):
    """jit-friendly CG core returning (x, r2, k); scope-aware like
    :func:`cg_solve` (the mesh core is itself traceable, so it inlines
    under the enclosing jit)."""
    spmv = _selected_spmv(a, bv, backend)
    if spmv.scope == "mesh":
        from repro.distributed import numerics as dnum
        return dnum.cg_mesh(a, bv, stop=stop, max_iters=max_iters,
                            variant=backend, loop=_cg_loop)

    def chip_spmv(p):                   # the selected variant, by name
        if spmv.name != "dia":
            return unwrap(registry.dispatch("solver_spmv", a, wrap(p),
                                            variant=spmv.name)), None
        ap, pap = registry.dispatch("solver_spmv", a, wrap(p),
                                    variant="dia", dot=True)
        return unwrap(ap), pap
    return _cg_loop(bv, stop, max_iters, spmv=chip_spmv)


cg_jit = call(_cg_jit_core, static_argnums=(3, 4))


@dataclasses.dataclass
class BlockCGResult:
    """Device-resident block-CG result: ``x`` is the (n, k) solution panel,
    ``residual_sq`` the per-RHS final squared residuals (k,)."""
    x: Dense
    iterations: jax.Array       # int32 scalar, on device
    residual_sq: jax.Array      # (k,) f32, on device


def cg_block_solve(a, b, *, stop: float = 1e-10, max_iters: int = 1000,
                   variant: Optional[str] = None,
                   rank_tol: float = 1e-7) -> BlockCGResult:
    """Multi-RHS conjugate gradients (block CG, O'Leary 1980) on the SpMM
    plane — the §3.4 listing widened to a (n, k) right-hand-side panel.

    One iteration does *one* SpMM (``S = A @ P``, each matrix element
    amortised over k FMAs — the arithmetic-intensity win the blocked-sparse
    plane exists for, DESIGN.md §9) and replaces CG's scalar α/β with k×k
    Gram solves, so the k systems share one Krylov space and converge in
    fewer iterations than k independent solves:

        γ = (PᵀS)⁻¹ (RᵀR)          X += P γ        R' = R − S γ
        δ = (RᵀR)⁻¹ (R'ᵀR')        P  = R' + P δ

    The SpMM is a registry dispatch: under an ambient O3/O4 mesh it runs
    row-sharded (``mesh_spmm``); ``variant=`` pins a formulation.  Stops
    when every RHS column's squared residual is below ``stop``.

    **Deflation** (closes the ROADMAP item): the classic block-CG failure
    mode is the residual block losing rank mid-solve — a column converges
    (its residual row/column of the Gram matrices goes to ~0) or columns
    become linearly dependent (duplicate/near-duplicate right-hand sides),
    and the plain ``linalg.solve`` of a singular k×k Gram matrix poisons
    *every* column.  Both Gram solves therefore run **rank-revealing**:
    well-converged columns (residual² ≤ ``stop``/100 — a hysteresis margin,
    so columns still flirting with the stop threshold keep contributing
    their shared Krylov directions instead of freezing their neighbours)
    are masked out of the system (identity-padded, so their γ/δ columns
    vanish and their x/r freeze), and the masked Gram factor is
    eigen-decomposed with eigenvalues below ``rank_tol``·λmax
    pseudo-inverted to zero — dependent search directions drop out of the
    shared Krylov space instead of stalling it.  On a well-conditioned
    full-rank panel both solves agree with the plain factorisation to
    floating-point precision.
    """
    bm = unwrap(wrap(b))
    if bm.ndim != 2:
        raise ValueError(f"cg_block_solve wants a (n, k) RHS panel, got "
                         f"shape {bm.shape}; use cg_solve for one vector")

    def aspmm(p):
        return unwrap(registry.dispatch("spmm", a, wrap(p), variant=variant))

    def rr_solve(g, rhs, active):
        """Rank-revealing solve of ``g @ out = rhs`` on the active columns.

        Inactive (converged) rows/columns are identity-padded and masked
        out of ``rhs``; the symmetrised remainder is eigen-factored and
        eigenvalues ≤ rank_tol·λmax invert to 0 (rank-deficient directions
        contribute nothing)."""
        am = active.astype(g.dtype)
        gm = g * (am[:, None] * am[None, :]) + jnp.diag(1.0 - am)
        gm = 0.5 * (gm + gm.T)              # PᵀAP / RᵀR: symmetric up to fp
        w, vec = jnp.linalg.eigh(gm)
        wmax = jnp.max(jnp.abs(w))
        inv = jnp.where(jnp.abs(w) > rank_tol * wmax, 1.0 / w, 0.0)
        rhs_m = rhs * (am[:, None] * am[None, :])
        return vec @ (inv[:, None] * (vec.T @ rhs_m))

    def cond(state):
        x, r, p, rtr, k = state
        return jnp.logical_and(jnp.max(jnp.diagonal(rtr)) > stop,
                               k < max_iters)

    def body(state):
        x, r, p, rtr, k = state
        # hysteresis: deflate only columns *well* below the stop threshold
        active = jnp.diagonal(rtr) > 0.01 * stop       # live RHS columns
        s = aspmm(p)                                   # S = A @ P   (n, k)
        gamma = rr_solve(p.T @ s, rtr, active)         # k×k
        x_new = x + p @ gamma
        r_new = r - s @ gamma
        rtr_new = r_new.T @ r_new
        delta = rr_solve(rtr, rtr_new, active)
        p_new = r_new + p @ delta
        return (x_new, r_new, p_new, rtr_new, k + 1)

    init = (jnp.zeros_like(bm), bm, bm, bm.T @ bm, jnp.int32(0))
    x, r, p, rtr, k = arbb_while(cond, body, init)
    return BlockCGResult(x=wrap(x), iterations=k,
                         residual_sq=jnp.diagonal(rtr))


def jacobi_solve(a_dense, b, *, iters: int = 200):
    """Jacobi iteration x <- D^-1 (b - (A - D) x)."""
    a = unwrap(wrap(a_dense))
    bv = unwrap(wrap(b))
    d = jnp.diagonal(a)
    off = a - jnp.diag(d)

    def body(_, x):
        return (bv - off @ x) / d

    x = jax.lax.fori_loop(0, iters, body, jnp.zeros_like(bv))
    return wrap(x)


def gauss_seidel_solve(a_dense, b, *, iters: int = 100):
    """Gauss-Seidel forward sweeps (serial per row — a recorded _for)."""
    a = unwrap(wrap(a_dense))
    bv = unwrap(wrap(b))
    n = a.shape[0]
    d = jnp.diagonal(a)

    def sweep(_, x):
        def row(i, x):
            s = bv[i] - a[i] @ x + a[i, i] * x[i]
            return x.at[i].set(s / d[i])
        return jax.lax.fori_loop(0, n, row, x)

    x = jax.lax.fori_loop(0, iters, sweep, jnp.zeros_like(bv))
    return wrap(x)
