"""Pallas TPU kernel: SpGEMM numeric phase — BSR × BSR block products.

The two-phase split (DESIGN.md §15) follows the many-core SpGEMM algorithm
of Deveci et al. (KokkosKernels, PAPERS.md): the *symbolic* phase — the
output's block-sparsity pattern — is host-side data-pipeline work
(:mod:`repro.sparse.spgemm`), and this module is the *numeric* phase only:
given both operands' BSR arrays plus the precomputed output pattern
(``c_cols``/``c_rowp``), fill the output's block values.

The traversal is Gustavson's row-wise form at block granularity, the same
recorded-``_for`` shape as :func:`repro.kernels.spmm.spmm_bsr_kernel` (the
paper's §3.2 dynamic-bounds ``_for``), one level deeper:

    for each output block-row i                      (the grid)
      acc[bs, m] = 0                                 (dense row accumulator)
      for p in a_rowp[i] .. a_rowp[i+1]:             (A's live blocks, _for)
        k = a_cols[p]
        for q in b_rowp[k] .. b_rowp[k+1]:           (B's row k, nested _for)
          acc[:, b_cols[q]·bs :+bs] += a_vals[p] @ b_vals[q]   (MXU FMA)
      for r in c_rowp[i] .. c_rowp[i+1]:             (gather the live tiles)
        c_vals[r] = acc[:, c_cols[r]·bs :+bs]

The accumulator is the *dense-row* variant of the per-row hash map: one
(bs, m) VMEM strip per block-row, indexed directly by block column — the
right trade below the VMEM ceiling (m ≲ 16K f32 columns), where the hash
probe sequence of the memory-constrained variant would only add control
flow.  Loop bounds and block-column indices read from whole-array refs
exactly like the SpMM kernel; on TPU hardware the production form hoists
them into scalar prefetch.  Correctness is validated in interpret mode
against :func:`repro.kernels.ref.spgemm_bsr_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["spgemm_bsr_kernel", "spgemm_bsr"]


def spgemm_bsr_kernel(a_rowp_ref, a_cols_ref, a_vals_ref,
                      b_rowp_ref, b_cols_ref, b_vals_ref,
                      c_rowp_ref, c_cols_ref, o_ref, *,
                      block: int, ncols: int):
    """One output block-row: nested recorded _for over A's live blocks and
    B's matching rows, FMAs into a dense (bs, m) row accumulator, then a
    gather of the live output tiles (see module docstring)."""
    i = pl.program_id(0)

    def outer(p, acc):
        ab = a_vals_ref[pl.dslice(p, 1), :, :][0]            # (bs, bs)
        k = a_cols_ref[p]

        def inner(q, acc):
            bb = b_vals_ref[pl.dslice(q, 1), :, :][0]        # (bs, bs)
            j = b_cols_ref[q]
            prod = jnp.dot(ab, bb, preferred_element_type=jnp.float32)
            tile = jax.lax.dynamic_slice(acc, (0, j * block),
                                         (block, block))
            return jax.lax.dynamic_update_slice(acc, tile + prod,
                                                (0, j * block))

        return jax.lax.fori_loop(b_rowp_ref[k], b_rowp_ref[k + 1],
                                 inner, acc)

    acc = jax.lax.fori_loop(a_rowp_ref[i], a_rowp_ref[i + 1], outer,
                            jnp.zeros((block, ncols), jnp.float32))

    def write(r, carry):
        j = c_cols_ref[r]
        tile = jax.lax.dynamic_slice(acc, (0, j * block), (block, block))
        o_ref[pl.ds(r, 1), :, :] = tile[None].astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(c_rowp_ref[i], c_rowp_ref[i + 1], write, 0)


def spgemm_bsr(
    a_vals: jax.Array, a_cols: jax.Array, a_rowp: jax.Array,
    b_vals: jax.Array, b_cols: jax.Array, b_rowp: jax.Array,
    c_cols: jax.Array, c_rowp: jax.Array,
    *,
    ncols: int,
    interpret: bool = False,
) -> jax.Array:
    """BSR × BSR numeric phase: returns ``c_vals (ncblocks, bs, bs)`` for
    the precomputed output pattern (``c_cols``/``c_rowp``).  ``ncols`` is
    B's dense column count (the accumulator width)."""
    na, bs, _ = a_vals.shape
    nbrows = a_rowp.shape[0] - 1
    nc = c_cols.shape[0]
    if nc == 0 or na == 0 or b_vals.shape[0] == 0:
        return jnp.zeros((nc, bs, bs), a_vals.dtype)
    grid = (nbrows,)

    return pl.pallas_call(
        functools.partial(spgemm_bsr_kernel, block=bs, ncols=ncols),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nbrows + 1,), lambda i: (0,)),
            pl.BlockSpec((na,), lambda i: (0,)),
            pl.BlockSpec((na, bs, bs), lambda i: (0, 0, 0)),
            pl.BlockSpec((b_rowp.shape[0],), lambda i: (0,)),
            pl.BlockSpec((b_cols.shape[0],), lambda i: (0,)),
            pl.BlockSpec((b_vals.shape[0], bs, bs), lambda i: (0, 0, 0)),
            pl.BlockSpec((nbrows + 1,), lambda i: (0,)),
            pl.BlockSpec((nc,), lambda i: (0,)),
        ],
        # whole-array output: each grid step stores only its row's tiles
        # (disjoint slots), so the revisited block is never double-written
        out_specs=pl.BlockSpec((nc, bs, bs), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, bs, bs), a_vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="spgemm_bsr",
        interpret=interpret,
    )(a_rowp, a_cols, a_vals, b_rowp, b_cols, b_vals, c_rowp, c_cols)
