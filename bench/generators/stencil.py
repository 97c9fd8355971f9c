"""Seeded variable-coefficient stencil operators, made on the device, and the
plain reference that the benchmark checks the program against.

The operator has the semantics of ``repro.numerics.sparse.stencil_3d``:
rows are grid points, x fastest (``i = x + nx*(y + ny*z)``); every stencil
edge carries a conductance ``c = 1 + 0.5*u`` with ``u`` uniform in [0, 1);
``A[i, j] = -c`` for an in-grid neighbour and ``A[i, i]`` sums the
conductances of all the point's stencil edges, those leaving the grid
included (Dirichlet).  So A is symmetric and diagonally dominant, strictly
on the rows at the boundary, hence positive definite.

The draw is counter-based: the conductance of an edge is a hash of
(seed, direction, lower row of the edge), so both ends of an edge read the
same value without any exchange, and each device can make its own rows of
a sharded operator.  DIA layout: ``diags[d][i]`` multiplies
``x[i + offsets[d]]`` (the program's ``DIA`` convention).

Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["steps", "offsets", "nnz", "key", "uniform", "normal",
           "direction_keys", "vector_keys", "diagonals", "maker", "make",
           "vectors", "spmv", "cg", "Operator", "DTYPES"]

_M32 = 0xFFFFFFFF
_GOLDEN = 0x9E3779B9

#: stream ids of the vectors drawn beside the operator's conductances
VECTOR_STREAM = 1 << 16

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def steps(points: int) -> list[tuple[int, int, int]]:
    """The stencil's neighbour steps (dx, dy, dz)."""
    if points == 7:
        return [(-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0), (0, 0, -1),
                (0, 0, 1)]
    if points == 27:
        return [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                for dx in (-1, 0, 1) if (dx, dy, dz) != (0, 0, 0)]
    raise ValueError(f"points must be 7 or 27, got {points}")


def _offset(s, grid) -> int:
    nx, ny, _ = grid
    return s[0] + nx * (s[1] + ny * s[2])


def offsets(grid, points: int) -> tuple[int, ...]:
    """Sorted DIA offsets, the main diagonal included."""
    return tuple(sorted({0} | {_offset(s, grid) for s in steps(points)}))


def nnz(grid, points: int) -> int:
    """Stored non-zeros of the operator: the main diagonal plus, for each
    step, the points whose neighbour lies inside the grid."""
    nx, ny, nz = grid
    total = nx * ny * nz
    for dx, dy, dz in steps(points):
        total += (nx - abs(dx)) * (ny - abs(dy)) * (nz - abs(dz))
    return total


def _fmix(h):
    """murmur3's 32-bit finaliser on a uint32 array (jnp or np)."""
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _fmix_int(h: int) -> int:
    h &= _M32
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M32
    return h ^ (h >> 16)


def key(seed: int, stream: int) -> int:
    """32-bit key of one stream of draws; any whole seed (both 32-bit
    halves of it count)."""
    seed = int(seed)
    k = _fmix_int((seed & _M32) ^ _fmix_int((seed >> 32) & _M32))
    return _fmix_int(k + (stream + 1) * _GOLDEN)


def uniform(k, rows, xp=jnp):
    """U[0, 1) drawn at integer ``rows`` (any sign) of the stream whose
    uint32 key is ``k``."""
    k = xp.asarray(k, xp.uint32)
    h = rows.astype(xp.int32).astype(xp.uint32) ^ k
    h = _fmix(_fmix(h) + k)
    return (h >> 8).astype(xp.float32) * xp.float32(2.0 ** -24)


def normal(k1, k2, rows, xp=jnp):
    """N(0, 1) at ``rows`` by Box-Muller on the uniform streams k1, k2."""
    u1 = 1.0 - uniform(k1, rows, xp)
    u2 = uniform(k2, rows, xp)
    return (xp.sqrt(-2.0 * xp.log(u1))
            * xp.cos(xp.float32(2 * np.pi) * u2)).astype(xp.float32)


def _up_steps(grid, points):
    """The steps with a positive offset; edge conductances are keyed by
    them, in this order."""
    return sorted((s for s in steps(points) if _offset(s, grid) > 0),
                  key=lambda s: _offset(s, grid))


def direction_keys(seed: int, grid, points: int) -> np.ndarray:
    """uint32 key of each edge direction's conductance stream."""
    return np.array([key(seed, j) for j in
                     range(len(_up_steps(grid, points)))], np.uint32)


def vector_keys(seed: int, count: int, stream0: int = 0) -> np.ndarray:
    """(count, 2) uint32 keys of the Box-Muller streams of ``count``
    vectors."""
    return np.array([[key(seed, VECTOR_STREAM + 2 * j + h) for h in (0, 1)]
                     for j in range(stream0, stream0 + count)], np.uint32)


def diagonals(rows, keys, grid, points: int, xp=jnp):
    """(ndiags, len(rows)) f32 diagonals at global ``rows``, with the
    conductance streams ``keys`` (:func:`direction_keys`).

    Written as one (ndiags, rows) elementwise expression plus the main
    diagonal's sum, so that the device writes the result in place and
    holds no per-diagonal temporaries."""
    nx, ny, nz = grid
    stream = {s: i for i, s in enumerate(_up_steps(grid, points))}
    by_off = {_offset(s, grid): s for s in steps(points)}
    offs = offsets(grid, points)

    def draws(s, r):
        off = _offset(s, grid)
        sup = s if off > 0 else tuple(-v for v in s)
        return 1.0 + 0.5 * uniform(keys[stream[sup]], r + min(off, 0), xp)

    diag = xp.zeros(rows.shape, xp.float32)
    for s in steps(points):                  # in-grid and Dirichlet edges
        diag = diag + draws(s, rows)
    # per-diagonal constants as (ndiags, 1) columns; the centre row's are
    # placeholders, replaced by ``diag`` below
    col = [by_off.get(o, (0, 0, 0)) for o in offs]
    dx, dy, dz = (xp.asarray([[c[j]] for c in col], xp.int32)
                  for j in range(3))
    shift = xp.asarray([[min(o, 0)] for o in offs], xp.int32)
    kcol = xp.asarray([[stream[c if o > 0 else tuple(-v for v in c)]
                        if o else 0] for o, c in zip(offs, col)], xp.int32)
    r = rows[None, :]
    x, y, z = r % nx, (r // nx) % ny, r // (nx * ny)
    c = 1.0 + 0.5 * uniform(xp.asarray(keys, xp.uint32)[kcol], r + shift, xp)
    ok = ((x + dx >= 0) & (x + dx < nx) & (y + dy >= 0) & (y + dy < ny)
          & (z + dz >= 0) & (z + dz < nz))
    entries = xp.where(ok, -c, xp.float32(0))
    is_center = xp.asarray([[o == 0] for o in offs])
    return xp.where(is_center, diag[None, :], entries)


@functools.lru_cache(maxsize=None)
def maker(grid, points: int, sharding=None):
    """``jit(keys -> diagonals)`` of the whole grid, laid out by
    ``sharding``: one compile per shape, whatever the seed."""
    n = grid[0] * grid[1] * grid[2]
    if n + 2 * _offset((1, 1, 1), grid) >= 2 ** 31:
        raise ValueError(f"grid {grid} has too many rows for int32 indices")
    return jax.jit(lambda keys: diagonals(jnp.arange(n, dtype=jnp.int32),
                                          keys, grid, points),
                   out_shardings=sharding)


def make(seed: int, grid, points: int, sharding=None):
    """The operator's (ndiags, n) f32 diagonals, made on the device in one
    jitted call, placed by ``sharding``."""
    grid = tuple(grid)
    return maker(grid, points, sharding)(direction_keys(seed, grid, points))


@functools.lru_cache(maxsize=None)
def _vector_maker(n: int, count: int, sharding=None):
    def build(keys):
        rows = jnp.arange(n, dtype=jnp.int32)
        return [normal(keys[j, 0], keys[j, 1], rows) for j in range(count)]
    return jax.jit(build, out_shardings=sharding)


def vectors(seed: int, n: int, count: int, stream0: int = 0, sharding=None):
    """``count`` seeded N(0, 1) vectors of length ``n``, made on the device."""
    return _vector_maker(n, count, sharding)(
        vector_keys(seed, count, stream0))


# ---------------------------------------------------------------------------
# plain reference: DIA SpMV as shifted multiply-adds, and textbook CG
# ---------------------------------------------------------------------------

def spmv(diags, offs, x):
    """``y[i] = sum_d diags[d, i] * x[i + offs[d]]``, out-of-range terms 0,
    in the dtype of ``diags`` and ``x``."""
    n = diags.shape[1]
    m = max(abs(o) for o in offs)
    xp = jnp.pad(x, (m, m))
    y = jnp.zeros((n,), jnp.result_type(diags.dtype, x.dtype))
    for d, off in enumerate(offs):
        y = y + diags[d] * xp[m + off:m + off + n]
    return y


def cg(diags, b, *, spmv, rtol: float, max_iters: int, dtype=jnp.float32):
    """Conjugate gradients from x0 = 0, every operation in ``dtype``, with
    ``spmv(diags, x)`` as the operator.  Stops when the recursive residual
    falls to ``rtol * |b|`` or after ``max_iters`` iterations.  Returns
    (x, iterations)."""
    a = diags.astype(dtype)
    b = b.astype(dtype)
    stop = (rtol * rtol) * jnp.vdot(b, b)

    def cond(s):
        return (s[3] > stop) & (s[4] < max_iters)

    def body(s):
        x, r, p, rr, k = s
        ap = spmv(a, p)
        alpha = rr / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = jnp.vdot(r, r)
        p = r + (rr_new / rr) * p
        return x, r, p, rr_new, k + 1

    init = (jnp.zeros_like(b), b, b, jnp.vdot(b, b), jnp.int32(0))
    x, _, _, _, k = jax.lax.while_loop(cond, body, init)
    return x, k


# ---------------------------------------------------------------------------
# a configuration on its chip
# ---------------------------------------------------------------------------

class Operator:
    """A configuration's operator made on its chip from ``seed``; without
    ``devices``, its shapes only."""

    def __init__(self, config: dict, seed: int = None, devices=None):
        self.grid = tuple(config["grid"])
        self.points = int(config["points"])
        if list(config["process_grid"]) != [1, 1, 1]:
            raise ValueError(f"one chip only: process grid "
                             f"{config['process_grid']}")
        self.n = self.grid[0] * self.grid[1] * self.grid[2]
        self.offsets = offsets(self.grid, self.points)
        if devices is not None:
            self.sharding = jax.sharding.SingleDeviceSharding(devices[0])
            self.diags = make(seed, self.grid, self.points, self.sharding)

    def spmv(self, diags, x):
        """The plain reference SpMV of this operator."""
        return spmv(diags, self.offsets, x)

    @property
    def nnz(self) -> int:
        return nnz(self.grid, self.points)

    @property
    def spmv_bytes(self) -> int:
        """Bytes one DIA SpMV must move: every stored diagonal entry, x and
        y, 4 B each: (ndiags + 2) * 4 per row."""
        return (len(self.offsets) + 2) * 4 * self.n

    def vectors(self, seed: int, count: int, stream0: int = 0):
        return vectors(seed, self.n, count, stream0, self.sharding)
