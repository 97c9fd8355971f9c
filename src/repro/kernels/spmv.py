"""Pallas TPU kernels: SpMV in ELL and DIA layouts (mod2as / CG, TPU-native).

Hardware adaptation (DESIGN.md §2): the paper's CSR formulation (after Bell &
Garland's CUDA kernels) is a per-row ragged gather loop — idiomatic for cache
hierarchies and warp-per-row GPUs, hostile to the TPU vector unit (no cheap
arbitrary gather, raggedness defeats tiling).  The TPU-native layout is
**padded ELL**: ``values``/``cols`` as rectangular (nrows, width) arrays.

The one gather the vector unit has is a lane gather inside a 128-lane vreg
row (``jnp.take_along_axis(..., axis=1)`` on an (8k, 128) tile).  So the
kernel reads ELL *slot-major*: slot ``w`` of 1024 consecutive rows is one
(8, 128) tile, and those rows' columns of that slot sit in a narrow range of
x for every matrix with locality (banded, stencil, reordered meshes).  x is
viewed as (n/128, 128) *sub-panels*; for each (row chunk, slot) the host-free
prologue computes the live sub-panel range ``[lo, hi]`` (scalar-prefetched),
and the kernel folds in one lane gather per sub-panel of that range:

    g = Σ_{q=lo..hi} where(cols >> 7 == q, gather(x[q], cols & 127), 0)
    y[chunk] += values[w, chunk] * g

Padding entries (value 0) are left out of the ranges.  x stays whole in VMEM
while it fits ``x_vmem_bytes``; beyond that it stays in HBM and each range
is read in windows of ``_WINDOW`` sub-panels by DMA ("x in panels").  The
cost per chunk is proportional to its column spread / 128, so a matrix with
no locality (uniform random columns) pays n/128 gathers per chunk and slot.

For the *banded* systems of the CG study (paper Table 2) the DIA kernel below
removes the gather entirely: each diagonal contributes a shifted FMA over a
row tile, reading a window of x tiles that fetches each tile once and makes
its own zero halo, or takes the rows a row shard's neighbours hold there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import metrics as obs_metrics

__all__ = ["spmv_ell_kernel", "spmv_ell", "spmv_dia_kernel", "spmv_dia"]

LANES = 128
#: rows of one (8, 128) slot tile — the unit a sub-panel range covers
CHUNK = 8 * LANES
#: sub-panels per DMA window when x is held in HBM
_WINDOW = 16
#: default VMEM budget for holding x whole (v5e has 128 MiB of VMEM)
X_VMEM_BYTES = 16 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def spmv_ell_kernel(lo_ref, hi_ref, vals_ref, cols_ref, x_ref, o_ref,
                    *scratch, width: int, chunks: int):
    """One row block of ``chunks`` (8, 128) row tiles, every ELL slot.

    ``x_ref`` is x as (n/128, 128) sub-panels: whole in VMEM, or in HBM with
    ``scratch = (window, sem)`` when it is read by DMA in ``_WINDOW``-row
    windows."""
    blk = pl.program_id(0)

    def fold_range(cols, lo, hi, g):
        def gather(q, xs, g):
            local = cols - q * LANES
            hit = (local >= 0) & (local < LANES)
            got = jnp.take_along_axis(jnp.broadcast_to(xs, cols.shape),
                                      jnp.where(hit, local, 0), axis=1)
            return g + jnp.where(hit, got, 0.0)

        if not scratch:
            return jax.lax.fori_loop(
                lo, hi + 1, lambda q, g: gather(q, x_ref[pl.ds(q, 1), :], g),
                g)
        win, sem = scratch

        def window(t, g):
            q0 = lo + t * _WINDOW
            cp = pltpu.make_async_copy(x_ref.at[pl.ds(q0, _WINDOW)], win, sem)
            cp.start()
            cp.wait()
            top = jnp.minimum(q0 + _WINDOW, hi + 1)
            return jax.lax.fori_loop(
                q0, top, lambda q, g: gather(q, win[pl.ds(q - q0, 1), :], g),
                g)

        nwin = jnp.maximum(hi - lo + _WINDOW, 0) // _WINDOW
        return jax.lax.fori_loop(0, nwin, window, g)

    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * 8, 8), 8)

        def slot(w, acc):
            m = (blk * chunks + c) * width + w
            cols = cols_ref[w, rows, :]
            g = fold_range(cols, lo_ref[m], hi_ref[m],
                           jnp.zeros(cols.shape, jnp.float32))
            return acc + vals_ref[w, rows, :].astype(jnp.float32) * g

        acc = jax.lax.fori_loop(0, width, slot,
                                jnp.zeros((8, LANES), jnp.float32))
        o_ref[rows, :] = acc.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


def spmv_ell(
    values: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    *,
    block_rows: int = 8 * CHUNK,
    x_vmem_bytes: int = X_VMEM_BYTES,
    interpret: bool = False,
) -> jax.Array:
    """ELL SpMV: ``y[i] = sum_w values[i, w] * x[cols[i, w]]``.

    Any shapes: rows pad to ``block_rows`` (a multiple of 1024), x to whole
    sub-panels.  x is held whole in VMEM up to ``x_vmem_bytes``, else read
    from HBM in windows."""
    nrows, width = values.shape
    assert cols.shape == (nrows, width), (cols.shape, values.shape)
    assert block_rows % CHUNK == 0, block_rows
    block_rows = min(block_rows, _round_up(nrows, CHUNK))
    npad = _round_up(nrows, block_rows)
    chunks = block_rows // CHUNK
    nblocks = npad // block_rows

    # slot-major tiles: (width, npad/128, 128); padding rows hold value 0
    vt = jnp.pad(values, ((0, npad - nrows), (0, 0))).T
    ct = jnp.pad(cols.astype(jnp.int32), ((0, npad - nrows), (0, 0))).T
    vt = vt.reshape(width, npad // LANES, LANES)
    ct = ct.reshape(width, npad // LANES, LANES)

    # live sub-panel range per (row chunk, slot); empty ranges are (big, -1)
    sub = (ct >> 7).reshape(width, npad // CHUNK, CHUNK)
    live = (vt != 0).reshape(sub.shape)
    big = jnp.iinfo(jnp.int32).max // 2
    lo = jnp.min(jnp.where(live, sub, big), axis=2).T.reshape(-1)
    hi = jnp.max(jnp.where(live, sub, -1), axis=2).T.reshape(-1)

    xrows = _round_up(x.shape[0], LANES) // LANES
    in_vmem = xrows * LANES * 4 <= x_vmem_bytes
    pad_rows = 0 if in_vmem else _WINDOW        # a window never runs off x
    xp = jnp.pad(x.astype(jnp.float32),
                 (0, (xrows + pad_rows) * LANES - x.shape[0]))
    xp = xp.reshape(xrows + pad_rows, LANES)
    if in_vmem:
        x_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
        scratch = []
        vmem = xp.size * 4
    else:
        x_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((_WINDOW, LANES), jnp.float32),
                   pltpu.SemaphoreType.DMA(())]
        vmem = _WINDOW * LANES * 4
    tile = pl.BlockSpec((width, block_rows // LANES, LANES),
                        lambda i, *_: (0, i, 0))
    vmem += 4 * width * block_rows * 4 + 2 * block_rows * 4

    y = pl.pallas_call(
        functools.partial(spmv_ell_kernel, width=width, chunks=chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nblocks,),
            in_specs=[tile, tile, x_spec],
            out_specs=pl.BlockSpec((block_rows // LANES, LANES),
                                   lambda i, *_: (i, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((npad // LANES, LANES), values.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem + (16 << 20),
        ),
        name="spmv_ell",
        interpret=interpret,
    )(lo, hi, vt, ct, xp)
    return y.reshape(-1)[:nrows]


def _lane_sums(v: jax.Array) -> jax.Array:
    """(1, k * 128) -> (1, 128): lane l sums the lanes of v that are l mod
    128, folded in halves over whole vregs (an odd vreg out goes to
    ``extra``), so the reduce never leaves the lanes it started in."""
    extra = jnp.zeros((1, LANES), v.dtype)
    while v.shape[1] > LANES:
        if v.shape[1] // LANES % 2:
            extra = extra + v[:, -LANES:]
            v = v[:, :-LANES]
        half = v.shape[1] // 2
        v = v[:, :half] + v[:, half:]
    return v + extra


def spmv_dia_kernel(diags_ref, x_ref, *refs, offsets: tuple[int, ...],
                    tile: int, tiles: int, tail: int, halo: bool,
                    dot: bool):
    """Banded SpMV, one grid step: y = sum_d diags[d] * x[row + off_d].

    ``win_ref`` holds three x tiles side by side, the window every shifted
    read is a *static slice* of — no rotation, no gather, pure VPU FMAs.
    Each step shifts it left one tile and appends the x tile it was given,
    so every x tile comes from HBM once.  Step 0 primes the window with the
    tile before x tile 0 and with x tile 0; step s > 0 computes row tile
    s - 1 over ``[x_{s-2}, x_{s-1}, x_s]``.  The tiles before the first and
    after the last, and the lanes of a ragged last tile past n (``tail``
    lanes hold x there, the rest Pallas leaves unspecified), are zeros, or
    with ``halo`` the rows of ``halo_ref``: the tile before, the last
    tile's lanes past n, and the tile after.  x and y blocks are (1, tile)
    or, for a flat vector, (tile,).

    With ``dot``, ``dot_ref`` is a (1, 1) output resident over the whole
    grid: ``x . y`` over these rows, x the window's centre tile (the rows'
    own x, never the halo).  Per-lane partial sums are carried in
    ``lane_ref``, (1, 128) f32, and the lanes summed at the last step; the
    lanes of a ragged last tile past n add nothing."""
    refs = list(refs)
    halo_ref = refs.pop(0) if halo else None
    o_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dot else None
    win_ref = refs.pop(0)
    lane_ref = refs.pop(0) if dot else None
    s = pl.program_id(0)

    def fill(row):
        if halo_ref is None:
            return jnp.zeros((1, tile), jnp.float32)
        return halo_ref[pl.ds(row, 1), :]

    @pl.when(s == 0)
    def _():
        win_ref[:, tile:2 * tile] = fill(0)

    @pl.when(s > 0)
    def _():
        win_ref[:, 0:tile] = win_ref[:, tile:2 * tile]
        win_ref[:, tile:2 * tile] = win_ref[:, 2 * tile:3 * tile]

    def x_tile():
        return x_ref[...].reshape(1, tile)

    @pl.when(s < tiles - 1)
    def _():
        win_ref[:, 2 * tile:3 * tile] = x_tile()

    @pl.when(s == tiles - 1)
    def _():
        x = x_tile()
        if tail < tile:
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            x = jnp.where(lane < tail, x, fill(1))
        win_ref[:, 2 * tile:3 * tile] = x

    @pl.when(s == tiles)
    def _():
        win_ref[:, 2 * tile:3 * tile] = fill(2)

    if dot:
        @pl.when(s == 0)
        def _():
            lane_ref[...] = jnp.zeros((1, LANES), jnp.float32)

    @pl.when(s > 0)
    def _():
        acc = jnp.zeros((1, tile), jnp.float32)
        for d, off in enumerate(offsets):        # static: unrolled in Mosaic
            acc += (diags_ref[pl.ds(d, 1), :].astype(jnp.float32)
                    * win_ref[:, pl.ds(tile + off, tile)])
        o_ref[...] = acc.reshape(o_ref.shape).astype(o_ref.dtype)
        if not dot:
            return
        xy = (win_ref[:, tile:2 * tile]
              * acc.astype(o_ref.dtype).astype(jnp.float32))

        @pl.when(s < tiles)
        def _():
            lane_ref[...] += _lane_sums(xy)

        @pl.when(s == tiles)
        def _():
            last = xy
            if tail < tile:
                lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
                last = jnp.where(lane < tail, xy, 0.0)
            lanes = lane_ref[...] + _lane_sums(last)
            dot_ref[...] = jnp.sum(lanes, axis=1, keepdims=True)


def _vmem_bytes(rows: int, lanes: int, dtype) -> int:
    """VMEM bytes of a (rows, lanes) block: rows fill whole (8, 128) f32
    tiles (16 rows for 2-byte types)."""
    itemsize = jnp.dtype(dtype).itemsize
    return _round_up(rows, 8 * max(1, 4 // itemsize)) * lanes * itemsize


def _dia_halo(lo: jax.Array, hi: jax.Array, tile: int, tail: int
              ) -> jax.Array:
    """The (3, tile) rows ``spmv_dia_kernel`` takes for its halo: the tile
    before x tile 0 (``lo`` in its last lanes), the lanes of the last tile
    past n (``tail`` lanes hold x there; ``hi`` follows), and the tile
    after it (the rest of ``hi``)."""
    m = lo.shape[0]
    return jnp.concatenate([
        jnp.zeros((tile - m,), jnp.float32), lo.astype(jnp.float32),
        jnp.zeros((tail,), jnp.float32), hi.astype(jnp.float32),
        jnp.zeros((2 * tile - tail - m,), jnp.float32)]).reshape(3, tile)


#: XLA tiles a 1-D f32 vector by 1024 and a (1, n) array by 128 lanes: the
#: two share their bytes only where n is a multiple of 1024
FLAT_TILE = 1024


def spmv_dia(
    diags: jax.Array,
    offsets: tuple[int, ...],
    x: jax.Array,
    *,
    halo: tuple[jax.Array, jax.Array] | None = None,
    dot: bool = False,
    interpret: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """DIA (banded) SpMV.  diags: (ndiags, n) aligned per repro.numerics.sparse.

    ``halo = (lo, hi)``: the max|offset| rows of x just before and just
    after these n rows (a row shard's neighbours' rows); None reads zeros
    there, the matrix's own edge.

    ``dot=True`` returns ``(y, x . y)``, the dot over these n rows taken
    while x and y are in VMEM (CG's p.Ap); y is the same as without it.

    The grid walks row tiles of 8192 lanes, raised to cover max|offset|
    and lowered for short vectors: a tile reads its own diagonals' columns
    plus the x tiles on either side, so one tile must span max|offset|.
    x and the diagonals go in unpadded: a ragged last tile is a partial
    block, its lanes past n masked in x and dropped from y.  The grid takes
    one step more than there are row tiles, and step s fetches x tile
    min(s, T - 1), which the kernel appends to its window
    (``spmv_dia_kernel``).  The VMEM limit is set from the blocks, which
    grow with the tile.

    x and y go in and out as (1, n), a bitcast of the vector where n is a
    multiple of :data:`FLAT_TILE`; otherwise as the vector itself in
    (tile,) blocks, with tiles of whole 1024s, so that XLA copies neither
    into another layout."""
    ndiags, n = diags.shape
    flat = n % FLAT_TILE != 0
    max_off = max((abs(o) for o in offsets), default=0)
    tile = _round_up(max(max_off, min(n, 8192)),
                     FLAT_TILE if flat else LANES)
    tiles = -(-n // tile)
    tail = n - (tiles - 1) * tile
    obs_metrics.METRICS.gauge("kernels.spmv_dia.x_bytes_per_launch").set(
        4 * tiles * tile)

    if flat:
        vec, vec_shape = (tile,), (n,)

        def row_tile(s):
            return (jnp.maximum(s - 1, 0),)

        def x_tile(s):
            return (jnp.minimum(s, tiles - 1),)
    else:
        vec, vec_shape = (1, tile), (1, n)

        def row_tile(s):
            return (0, jnp.maximum(s - 1, 0))

        def x_tile(s):
            return (0, jnp.minimum(s, tiles - 1))

    operands = [diags, x.astype(jnp.float32).reshape(vec_shape)]
    in_specs = [pl.BlockSpec((ndiags, tile), lambda s: (0, row_tile(s)[-1])),
                pl.BlockSpec(vec, x_tile)]
    # double-buffered diagonal, x and y blocks, and the window
    vmem = 2 * (_vmem_bytes(ndiags, tile, diags.dtype)
                + _vmem_bytes(1, tile, jnp.float32)
                + _vmem_bytes(1, tile, diags.dtype)) \
        + _vmem_bytes(1, 3 * tile, jnp.float32)
    if halo is not None:
        lo, hi = halo
        assert lo.shape == hi.shape == (max_off,), (lo.shape, hi.shape)
        operands.append(_dia_halo(lo, hi, tile, tail))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        vmem += _vmem_bytes(3, tile, jnp.float32)

    out_specs = pl.BlockSpec(vec, row_tile)
    out_shape = jax.ShapeDtypeStruct(vec_shape, diags.dtype)
    scratch = [pltpu.VMEM((1, 3 * tile), jnp.float32)]
    if dot:
        obs_metrics.METRICS.counter("kernels.spmv_dia.dot_fused").inc()
        out_specs = (out_specs, pl.BlockSpec((1, 1), lambda s: (0, 0)))
        out_shape = (out_shape, jax.ShapeDtypeStruct((1, 1), jnp.float32))
        scratch.append(pltpu.VMEM((1, LANES), jnp.float32))

    out = pl.pallas_call(
        functools.partial(spmv_dia_kernel, offsets=tuple(offsets), tile=tile,
                          tiles=tiles, tail=tail, halo=halo is not None,
                          dot=dot),
        grid=(tiles + 1,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (8 << 20)),
        name="spmv_dia",
        interpret=interpret,
    )(*operands)
    if dot:
        y, xy = out
        # Where y's one consumer scales it (CG's alpha * Ap), XLA would move
        # this reshape past the multiply and run the multiply as a pass of
        # its own over the (1, n) layout; the barrier keeps y flat for it.
        return jax.lax.optimization_barrier(y.reshape(n)), xy.reshape(())
    return out.reshape(n)
