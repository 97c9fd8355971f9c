"""Pallas TPU kernels: SpMM — sparse matrix × dense multi-RHS panel.

The single-vector SpMV kernels (repro.kernels.spmv) are bandwidth-bound:
every matrix element is read once per *one* multiply-add.  With a dense
right-hand-side panel ``X (n, k)`` each element amortises over ``k`` FMAs —
the arithmetic-intensity lever Deveci et al. identify as the scalable form
of sparse numerics (PAPERS.md), and the reason the blocked-sparse plane
(DESIGN.md §9) is built around SpMM rather than more SpMV variants.

Two layouts, two duals of the same adaptation:

    ELL  ``y[i, :] += Σ_w values[i, w] · X[cols[i, w], :]`` — the SpMV
         rectangular gather widened to a panel: the gather now fetches
         *rows* of X (VMEM-resident, one RHS panel per grid step), so each
         gathered row feeds ``bn`` lanes of FMAs instead of one.
    BSR  ``y[I, :] += Σ_p values[p] @ X[cols[p]·bs : +bs, :]`` — block-CSR:
         the inner step is a dense (bs, bs) × (bs, bn) product on the MXU;
         the only irregularity left is *which* blocks, walked with a
         recorded ``fori_loop`` over this block-row's ``rowp`` section
         (the paper's §3.2 dynamic-bounds ``_for``, at block granularity).

The BSR kernel reads its loop bounds and block-column indices from
whole-array refs; on TPU hardware the production form hoists them into
scalar prefetch (``pltpu.PrefetchScalarGridSpec``) so the DMA for block
``p+1`` can issue while block ``p`` multiplies — correctness here is
validated in interpret mode against :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["spmm_ell_kernel", "spmm_ell", "spmm_bsr_kernel", "spmm_bsr"]


def spmm_ell_kernel(values_ref, cols_ref, x_ref, o_ref):
    """One (row_block, rhs_panel) output tile; accumulates over width."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    vals = values_ref[...]                        # (bm, bw)
    cols = cols_ref[...]                          # (bm, bw) int32
    x = x_ref[...]                                # (n, bn) panel, VMEM
    gathered = jnp.take(x, cols, axis=0)          # (bm, bw, bn) row gather
    o_ref[...] += jnp.sum(vals[..., None] * gathered, axis=1)


def spmm_ell(
    values: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    *,
    block_rows: int = 8,
    block_width: int = 128,
    block_rhs: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """ELL SpMM: ``y[i, j] = sum_w values[i, w] * x[cols[i, w], j]``."""
    nrows, width = values.shape
    n, k = x.shape
    assert cols.shape == (nrows, width)
    assert (nrows % block_rows == 0 and width % block_width == 0
            and k % block_rhs == 0), ((nrows, width, k),
                                      (block_rows, block_width, block_rhs))
    grid = (nrows // block_rows, k // block_rhs, width // block_width)

    return pl.pallas_call(
        spmm_ell_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_width), lambda i, j, w: (i, w)),
            pl.BlockSpec((block_rows, block_width), lambda i, j, w: (i, w)),
            pl.BlockSpec((n, block_rhs), lambda i, j, w: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_rhs),
                               lambda i, j, w: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nrows, k), values.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="spmm_ell",
        interpret=interpret,
    )(values, cols, x)


def spmm_bsr_kernel(rowp_ref, cols_ref, values_ref, x_ref, o_ref, *,
                    block: int):
    """One (block-row, rhs_panel) tile: recorded _for over the row's blocks,
    each step a dense (bs, bs) @ (bs, bn) MXU product."""
    i = pl.program_id(0)
    start = rowp_ref[i]
    stop = rowp_ref[i + 1]

    def body(p, acc):
        blk = values_ref[pl.dslice(p, 1), :, :][0]          # (bs, bs)
        c = cols_ref[p]
        xb = x_ref[pl.ds(c * block, block), :]              # (bs, bn)
        return acc + jnp.dot(blk, xb, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        start, stop, body,
        jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


def spmm_bsr(
    values: jax.Array,
    cols: jax.Array,
    rowp: jax.Array,
    x: jax.Array,
    *,
    block_rhs: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """BSR SpMM: block-tile FMAs on the MXU (see module docstring)."""
    nblocks, bs, bs2 = values.shape
    n, k = x.shape
    nbrows = rowp.shape[0] - 1
    assert bs == bs2, values.shape
    assert k % block_rhs == 0, (k, block_rhs)
    if nblocks == 0:
        return jnp.zeros((nbrows * bs, k), values.dtype)
    grid = (nbrows, k // block_rhs)

    return pl.pallas_call(
        functools.partial(spmm_bsr_kernel, block=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nbrows + 1,), lambda i, j: (0,)),
            pl.BlockSpec((nblocks,), lambda i, j: (0,)),
            pl.BlockSpec((nblocks, bs, bs), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((n, block_rhs), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bs, block_rhs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nbrows * bs, k), values.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="spmm_bsr",
        interpret=interpret,
    )(rowp, cols, values, x)
