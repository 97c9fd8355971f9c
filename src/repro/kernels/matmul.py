"""Pallas TPU kernel: blocked dense matmul (mod2am's hot spot, TPU-native).

Hardware adaptation (DESIGN.md §2): the paper's winning ArBB variant
(arbb_mxm2b) restructures the matmul into an unrolled recorded loop of rank-1
updates — a cache-blocking trick for SIMD CPUs.  The MXU wants the dual
formulation: *K-panel inner products* accumulated in an f32 VMEM scratch.
This kernel is that formulation:

    grid = (M/bm, N/bn, K/bk)        K innermost ("arbitrary" = sequential)
    A tile (bm, bk) and B tile (bk, bn) in VMEM per step   [BlockSpec]
    acc (bm, bn) f32 VMEM scratch, zeroed at k==0, flushed at k==K/bk-1

Block defaults (128, 128, 128) are MXU-aligned (128x128 systolic array) and
keep the working set at 3 * 128*128*4B = 192 KiB ≪ 16 MiB VMEM, leaving room
for double-buffered pipelining by the Mosaic compiler.

The paper's unroll-inside-recorded-loop insight survives as ``dimension
semantics``: M/N grid axes are 'parallel', K is 'arbitrary' — exactly the
"recorded serial loop over K panels" the ArBB version hand-built.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_kernel", "matmul"]


def matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    """One (bm, bn) output tile; accumulates over the K grid dimension."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a, b = a_ref[...], b_ref[...]
    # f32 operands get f32 products (the MXU's default is one bf16 pass)
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    acc_ref[...] += jnp.dot(a, b, precision=precision,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``a @ b`` via the blocked Pallas kernel.

    Shapes must tile evenly (the ops.py wrapper pads); dtypes bf16/f32 in,
    f32 accumulation always.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    out_dtype = out_dtype or a.dtype
    grid = (m // block_m, n // block_n, k // block_k)

    return pl.pallas_call(
        functools.partial(matmul_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="matmul",
        interpret=interpret,
    )(a, b)
