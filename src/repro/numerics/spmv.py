"""mod2as — sparse matrix-vector multiplication.

Four implementations spanning paper-faithful -> TPU-native:

    arbb_spmv1   the paper's §3.2 port, literally: ``map()`` over rows with a
                 recorded ``_for`` whose bounds come from rowp sections.
                 (emap + arbb_for with traced bounds.)
    arbb_spmv2   the paper's "contiguous" improvement.  The paper walks two
                 pointers for contiguous runs; the vectorised analogue is a
                 flat segmented formulation — one elementwise
                 gather-multiply over nnz + segment-sum by row, which is
                 exactly what 'exploit contiguity' buys on a vector machine.
    spmv_ell     ELL layout: rectangular gather-multiply-reduce (the layout
                 the Pallas kernel mirrors; DESIGN.md adaptation note 2).
    spmv_dia     banded/diagonal: shifted FMAs, gather-free (CG fast path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import Dense, arbb_for, call, emap, section, unwrap, wrap
from repro.core import registry
from repro.core.registry import Cost
from repro.numerics.sparse import CSR, DIA, ELL, csr_row_ids

__all__ = ["arbb_spmv1", "arbb_spmv2", "spmv_ell", "spmv_dia",
           "spmv1", "spmv2", "spmv_ell_jit", "spmv_dia_jit",
           "csr_row_reduce", "dia_panel"]


def csr_row_reduce(matvals, indx, x):
    """The paper's per-row ``local::reduce``: a recorded ``_for`` over
    ``[rowpi, rowpj)`` gathering ``matvals[i] * x[indx[i]]``.

    Returned as a scalar function of the row-pointer pair so it can be
    mapped — by :func:`emap` here, or per row-shard inside the mesh-scoped
    SpMV (:mod:`repro.distributed.numerics`)."""
    def reduce(ri, rj):
        def body(i, acc):
            return acc + matvals[i] * x[indx[i]]
        # dynamic (traced) bounds: lax.fori_loop lowers to while_loop
        return arbb_for_dynamic(ri, rj, body, jnp.zeros((), matvals.dtype))
    return reduce


def arbb_spmv1(csr: CSR, invec: Dense) -> Dense:
    """Faithful port of the paper's arbb_spmv1 (after Bell & Garland [10]).

    ``map(local::reduce)(outvec, matvals, invec, indx, rowpi, rowpj)`` with a
    recorded per-row ``_for`` that gathers ``matvals[i] * invec[indx[i]]``.
    """
    invec = wrap(invec)
    nrows = csr.shape[0]
    rowp = Dense(csr.rowp)
    rowpi = section(rowp, 0, nrows)      # rowp[0 .. nrows)
    rowpj = section(rowp, 1, nrows)      # rowp[1 .. nrows+1)

    reduce = csr_row_reduce(csr.matvals, csr.indx, unwrap(invec))
    out = emap(reduce, in_axes=(0, 0))(rowpi, rowpj)
    return wrap(out)


def arbb_for_dynamic(start, stop, body, init):
    """A recorded _for with data-dependent (traced) bounds, as the paper's
    ``_for (i = rowpi, i != rowpj, ++i)`` requires."""
    import jax.lax as lax
    return lax.fori_loop(unwrap(start), unwrap(stop), body, init)


def arbb_spmv2(csr: CSR, invec: Dense) -> Dense:
    """The 'contiguity-exploiting' variant, vectorised.

    Flat form: one fused gather-multiply over the nnz stream followed by a
    row segment-sum.  On contiguous runs the gather becomes a unit-stride
    read — the same property the paper's two-pointer rewrite exploits.
    """
    invec = wrap(invec)
    nrows = csr.shape[0]
    x = unwrap(invec)
    prod = csr.matvals * x[csr.indx]                      # elementwise stream
    seg = csr_row_ids(csr.rowp, prod.shape[0])
    out = jax.ops.segment_sum(prod, seg, num_segments=nrows)
    return wrap(out)


def spmv_ell(ell: ELL, invec: Dense) -> Dense:
    """ELL SpMV: rectangular gather + row reduction (pure-jnp reference for
    the Pallas kernel in repro.kernels.spmv)."""
    x = unwrap(wrap(invec))
    gathered = x[ell.cols]                 # (nrows, width)
    return wrap(jnp.sum(ell.values * gathered, axis=1))


def spmv_dia(dia: DIA, invec: Dense, *, dot: bool = False):
    """DIA SpMV: y_i = sum_d diag_d[i] * x[i + off_d] — shifted FMAs only.

    Gather-free, the TPU-native banded path (DESIGN.md §2).  It runs the
    ``spmv_dia`` kernel op, so the plane decides the body: the Pallas
    row-tile kernel on TPU, the shifted-FMA reference elsewhere.
    ``dot=True`` returns ``(y, x . y)``, the dot taken by the same op."""
    from repro.kernels import ops          # lazy: kernels import numerics

    x = unwrap(wrap(invec))
    if dot:
        with ops.spmv_dia_dots() as dots:
            y = ops.spmv_dia(dia.diags, dia.offsets, x)
        return wrap(y), dots[0]
    return wrap(ops.spmv_dia(dia.diags, dia.offsets, x))


def dia_panel(diags, offsets: tuple, xf, row0=0):
    """``y[i, :] = Σ_d diags[d][i] · xf[row0 + i + offsets[d], :]`` — the
    DIA shifted-FMA loop over a 2-D RHS panel, the one encoding of the DIA
    alignment convention shared by the chip spmm variant (``row0=0``;
    repro.sparse.spmm) and the row-sharded mesh local (``row0`` = this
    shard's global row offset; repro.distributed.numerics).  The offsets
    are static, so the loop unrolls at trace time; out-of-range reads
    resolve to 0 via edge padding."""
    n_local = diags.shape[1]
    maxoff = max((abs(o) for o in offsets), default=0)
    xp = jnp.pad(xf, ((maxoff, maxoff), (0, 0)))
    y = jnp.zeros((n_local, xf.shape[1]),
                  jnp.result_type(diags.dtype, xf.dtype))
    for d, off in enumerate(offsets):
        seg = jax.lax.dynamic_slice(
            xp, (row0 + off + maxoff, 0), (n_local, xf.shape[1]))
        y = y + diags[d][:, None] * seg
    return y


spmv1 = call(arbb_spmv1)
spmv2 = call(arbb_spmv2)
spmv_ell_jit = call(spmv_ell)
spmv_dia_jit = call(spmv_dia)


# The solver-facing SpMV variants (the paper runs arbb_spmv1/arbb_spmv2; we
# add the layout-specialised paths).  These are DSL-level formulations
# (plane=None — they lower under any kernel plane); ``accepts`` keys on the
# matrix layout — and on a 1-D x: a 2-D multi-RHS x routes to the spmm
# plane instead (repro.sparse.spmm) — so auto-selection picks the strongest
# formulation the operand admits, and costs order CSR variants by the
# paper's own measured ranking (spmv2's contiguity rewrite beats spmv1).
def _takes(layout):
    return lambda m, v, **_: (isinstance(m, layout)
                              and getattr(unwrap(v), "ndim", 1) == 1)


# the ladder derives from the registry's named layout ranks (Cost.DIA <
# Cost.ELL < Cost.CSR — one source of truth with the spmm plane); spmv1,
# the paper's naive port, ranks behind its own contiguity rewrite.
registry.register("solver_spmv", "spmv1", arbb_spmv1, cost=2 * Cost.CSR,
                  accepts=_takes(CSR),
                  doc="paper §3.2 port: map() over rows + recorded _for")
registry.register("solver_spmv", "spmv2", arbb_spmv2, cost=Cost.CSR,
                  accepts=_takes(CSR),
                  doc="contiguity-exploiting flat segmented form")
registry.register("solver_spmv", "ell", spmv_ell, cost=Cost.ELL,
                  accepts=_takes(ELL),
                  doc="rectangular ELL gather-multiply-reduce")
registry.register("solver_spmv", "dia", spmv_dia, cost=Cost.DIA,
                  accepts=_takes(DIA),
                  doc="banded shifted-FMA, gather-free (CG fast path)")
