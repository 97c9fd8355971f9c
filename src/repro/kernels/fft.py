"""Pallas TPU kernel: split-stream FFT butterfly stage (mod2f, TPU-native).

Hardware adaptation (DESIGN.md §2): the split-stream algorithm was designed
for GPU stream processors — each stage reads the even/odd interleave and
writes two contiguous halves, with no scatter.  On TPU the even/odd split
is done by the caller as a (n/2, 2) -> (2, n/2) transpose, so the kernel
sees *lane-dense* streams — (even, odd) stacked for re and for im, each
viewed as (2, n/2/128, 128) — and writes (up, down) stacked the same way:

    up   = even + odd
    down = (even - odd) * tw         (cat(up, down) is the stage's output)

so ``cat(up, down)`` is a free reshape of each output.

Complex arithmetic is explicit re/im (Mosaic has no native complex), so one
stage = one fused VPU pass: 4 mul + 6 add per butterfly.  The grid tiles the
n/2 butterflies in blocks of ``block_rows`` full vreg rows.

The stage is applied log2(n) times by :func:`repro.kernels.ops.fft` with the
bit-reversed twiddle table of :mod:`repro.numerics.fft` (prefix property ⇒ the
same table serves every stage; stage s uses its first n/2^{s+1} entries tiled).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fft_stage_kernel", "fft_stage"]

LANES = 128


def fft_stage_kernel(re_ref, im_ref, twr_ref, twi_ref, ore_ref, oim_ref):
    """One tile of butterflies: rows 0/1 of the streams are even/odd in,
    up/down out."""
    er, orr = re_ref[0], re_ref[1]
    ei, oi = im_ref[0], im_ref[1]
    twr, twi = twr_ref[...], twi_ref[...]
    ore_ref[0] = er + orr
    oim_ref[0] = ei + oi
    dr = er - orr
    di = ei - oi
    ore_ref[1] = dr * twr - di * twi
    oim_ref[1] = dr * twi + di * twr


def fft_stage(
    data_re: jax.Array,     # (2, n/2): even stream row 0, odd stream row 1
    data_im: jax.Array,
    tw_re: jax.Array,       # (n/2,) stage twiddles (already tiled)
    tw_im: jax.Array,
    *,
    block_rows: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Apply one split-stream stage.  Returns ``(out_re, out_im)``, each
    (2, n/2) with up in row 0 and down in row 1, so ``out.reshape(n)`` is
    the paper's ``cat(up, down)``."""
    half = data_re.shape[1]
    lanes = min(half, LANES)
    assert half % lanes == 0, half
    rows = half // lanes
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    pair = pl.BlockSpec((2, block_rows, lanes), lambda c: (0, c, 0))
    tw = pl.BlockSpec((block_rows, lanes), lambda c: (c, 0))
    out = jax.ShapeDtypeStruct((2, rows, lanes), data_re.dtype)
    ore, oim = pl.pallas_call(
        fft_stage_kernel,
        grid=(rows // block_rows,),
        in_specs=[pair, pair, tw, tw],
        out_specs=[pair, pair],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        name="fft_stage",
        interpret=interpret,
    )(data_re.reshape(2, rows, lanes), data_im.reshape(2, rows, lanes),
      tw_re.reshape(rows, lanes), tw_im.reshape(rows, lanes))
    return ore.reshape(2, half), oim.reshape(2, half)
