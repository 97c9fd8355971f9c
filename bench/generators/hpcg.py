"""HPCG 3.1's 27-point operator row-sharded over a mesh of chips, seeded
right-hand sides, and the plain reference the benchmark checks the
program against.

The operator is ``GenerateProblem_ref.cpp``'s: rows are the points of the
global nx x ny x nz grid, x fastest (``i = x + nx*(y + ny*z)``); the
diagonal is 26 and every in-grid neighbour of the 27-point stencil is -1
(boundary rows have fewer neighbours and keep 26).  The process grid
splits z only, so chip k holds rows [k*n/P, (k+1)*n/P): the row shards of
``P(None, "data")`` diagonals and ``P("data")`` vectors.  DIA layout:
``diags[d][i]`` multiplies ``x[i + offsets[d]]`` (the program's ``DIA``
convention).

Each chip makes its own rows (``shard_map``); nothing is made whole.
The right-hand sides are b = A x* with x* = 1 + N(0, 1) drawn by
``stencil``'s counter-based streams: HPCG's exact solution is all ones,
and the noise makes each seed's b its own.

The reference imports nothing of the program: its SpMV concatenates the
max|offset| rows each neighbour sends (two ``ppermute``s) around the
shard's x and sums shifted multiply-adds; its CG is the textbook
iteration with f32 dots at ``precision="highest"``, run sharded so that it
fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bench.harness import ROOT, load_module

stencil = load_module(ROOT, "generators", "stencil")

__all__ = ["AXIS", "offsets", "diagonals", "halo", "spmv_local", "Operator",
           "DTYPES"]

#: the mesh axis the rows shard over
AXIS = "data"
POINTS = 27
DIAGONAL, NEIGHBOUR = 26.0, -1.0

DTYPES = stencil.DTYPES


def offsets(grid) -> tuple[int, ...]:
    return stencil.offsets(grid, POINTS)


def diagonals(rows, grid, xp=jnp):
    """(27, len(rows)) f32 diagonals at global ``rows``: 26 on the main
    diagonal, -1 where the neighbour lies in the grid, else 0.  One
    elementwise expression over (27, rows), so the device writes it in
    place."""
    nx, ny, nz = grid
    by_off = {stencil._offset(s, grid): s for s in stencil.steps(POINTS)}
    col = [by_off.get(o, (0, 0, 0)) for o in offsets(grid)]
    dx, dy, dz = (xp.asarray([[c[j]] for c in col], xp.int32)
                  for j in range(3))
    r = rows[None, :]
    x, y, z = r % nx, (r // nx) % ny, r // (nx * ny)
    ok = ((x + dx >= 0) & (x + dx < nx) & (y + dy >= 0) & (y + dy < ny)
          & (z + dz >= 0) & (z + dz < nz))
    center = xp.asarray([[o == 0] for o in offsets(grid)])
    return xp.where(center, xp.float32(DIAGONAL),
                    xp.where(ok, xp.float32(NEIGHBOUR), xp.float32(0)))


def _rows(n_local: int):
    """This shard's global row indices, inside shard_map."""
    k = jax.lax.axis_index(AXIS)
    return k * n_local + jnp.arange(n_local, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# plain reference: halo exchange, shifted multiply-adds, textbook CG
# ---------------------------------------------------------------------------

def halo(x, rows: int, shards: int):
    """(lo, hi): the ``rows`` rows of x just before and just after this
    shard's, from shards k - 1 and k + 1; zeros past the ends."""
    lo = jax.lax.ppermute(x[x.shape[0] - rows:], AXIS,
                          [(k, k + 1) for k in range(shards - 1)])
    hi = jax.lax.ppermute(x[:rows], AXIS,
                          [(k + 1, k) for k in range(shards - 1)])
    return lo, hi


def spmv_local(diags, offs, x, lo, hi):
    """``y[i] = sum_d diags[d, i] * x[i + offs[d]]`` over one shard's rows,
    with ``lo``/``hi`` the max|offset| rows of x before and after them."""
    n = diags.shape[1]
    m = max(abs(o) for o in offs)
    xp = jnp.concatenate([lo, x, hi])
    y = jnp.zeros((n,), jnp.result_type(diags.dtype, x.dtype))
    for d, off in enumerate(offs):
        y = y + diags[d] * xp[m + off:m + off + n]
    return y


def _dot(a, b):
    return jax.lax.psum(jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST),
                        AXIS)


# ---------------------------------------------------------------------------
# a configuration on its chips
# ---------------------------------------------------------------------------

def _shard_map(mesh, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.lru_cache(maxsize=None)
def _diag_maker(mesh, grid, n_local: int):
    """``jit(() -> diagonals)``, each chip making its own rows."""
    return jax.jit(_shard_map(mesh, lambda: diagonals(_rows(n_local), grid),
                              (), P(None, AXIS)))


@functools.lru_cache(maxsize=None)
def _star_maker(mesh, n_local: int, count: int):
    """``jit(keys -> [x*])``, x* = 1 + N(0, 1) at each chip's rows."""
    def stars(keys):
        rows = _rows(n_local)
        return [1.0 + stencil.normal(keys[j, 0], keys[j, 1], rows)
                for j in range(count)]
    return jax.jit(_shard_map(mesh, stars, P(), [P(AXIS)] * count))


class Operator:
    """A configuration's operator made on its chips from ``seed``; without
    ``devices``, its shapes only."""

    def __init__(self, config: dict, seed: int = None, devices=None):
        self.grid = tuple(config["grid"])
        self.process_grid = tuple(config["process_grid"])
        if self.process_grid[:2] != (1, 1):
            raise ValueError(f"z-slab process grids only: "
                             f"{self.process_grid}")
        self.shards = self.process_grid[2]
        self.n = self.grid[0] * self.grid[1] * self.grid[2]
        self.offsets = offsets(self.grid)
        self.max_offset = max(abs(o) for o in self.offsets)
        if self.n % self.shards or self.n + 2 * self.max_offset >= 2 ** 31:
            raise ValueError(f"grid {self.grid} does not split into "
                             f"{self.shards} int32-indexed row shards")
        self.n_local = self.n // self.shards
        if devices is None:
            return
        if len(devices) != self.shards:
            raise ValueError(f"{len(devices)} devices for {self.shards} "
                             f"row shards")
        self.mesh = jax.sharding.Mesh(
            np.asarray(devices).reshape(self.shards, 1), (AXIS, "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self.diags = _diag_maker(self.mesh, self.grid, self.n_local)()

    def spmv(self, diags, x):
        """The plain reference SpMV, sharded: halo exchange, then shifted
        multiply-adds."""
        offs, m, shards = self.offsets, self.max_offset, self.shards

        def local(d, xl):
            return spmv_local(d, offs, xl, *halo(xl, m, shards))
        return _shard_map(self.mesh, local, (P(None, AXIS), P(AXIS)),
                          P(AXIS))(diags, x)

    def rhs(self, seed: int, count: int):
        """``count`` right-hand sides b = A x*, x* = 1 + N(0, 1) seeded,
        made on the chips by rows."""
        stars = _star_maker(self.mesh, self.n_local, count)(
            stencil.vector_keys(seed, count))
        spmv = jax.jit(self.spmv)
        return [spmv(self.diags, xs) for xs in stars]

    def cg(self, diags, b, *, max_iters: int, dtype=jnp.float32):
        """Textbook CG from x0 = 0 for exactly ``max_iters`` iterations,
        every operation in ``dtype``, dots at HIGHEST precision, sharded by
        rows.  Returns (x, iterations)."""
        offs, m, shards = self.offsets, self.max_offset, self.shards

        def local(d, bl):
            a = d.astype(dtype)
            bl = bl.astype(dtype)

            def body(_, s):
                x, r, p, rr = s
                ap = spmv_local(a, offs, p, *halo(p, m, shards))
                alpha = rr / _dot(p, ap)
                x = x + alpha * p
                r = r - alpha * ap
                rr_new = _dot(r, r)
                return x, r, r + (rr_new / rr) * p, rr_new

            init = (jnp.zeros_like(bl), bl, bl, _dot(bl, bl))
            return jax.lax.fori_loop(0, max_iters, body, init)[0]

        x = _shard_map(self.mesh, local, (P(None, AXIS), P(AXIS)), P(AXIS))(
            diags, b)
        return x, jnp.int32(max_iters)

    @property
    def nnz(self) -> int:
        return stencil.nnz(self.grid, POINTS)

    @property
    def spmv_bytes(self) -> int:
        """Bytes one chip's DIA SpMV must move: every stored diagonal entry
        of its rows, x and y, 4 B each: (ndiags + 2) * 4 per row."""
        return (len(self.offsets) + 2) * 4 * self.n_local
