"""Sparse-matrix storage for mod2as / CG.

The paper uses the 3-array CSR format (§3.2: matvals / indx / rowp).  CSR is
kept as the canonical/oracle format; two TPU-adapted layouts are derived from
it (DESIGN.md §2 "hardware adaptation"):

    ELL  — fixed nnz-per-row padding; turns the per-row ragged gather loop
           into rectangular (nrows, width) arrays → vectorisable, and the
           layout the Pallas SpMV kernel consumes (width padded to 128).
    DIA  — diagonal storage for the banded CG systems (paper Table 2);
           SpMV becomes `bw` shifted vector FMAs with NO gather at all.

Construction is host-side numpy (this is data-pipeline work, not kernel work);
the containers hold device arrays.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CSR", "ELL", "DIA", "random_sparse", "banded_spd",
           "csr_from_dense", "ell_from_csr", "dia_from_dense",
           "csr_row_ids", "Stencil3D", "stencil_3d"]


def csr_row_ids(rowp: jax.Array, count: int) -> jax.Array:
    """Row id per stored entry: entry ``p`` belongs to the row ``i`` with
    ``rowp[i] <= p < rowp[i+1]`` — the segment ids every flat CSR-style
    formulation (element or block granular) feeds to ``segment_sum``."""
    return jnp.searchsorted(rowp[1:], jnp.arange(count), side="right")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSR:
    """3-array CSR exactly as the paper describes it."""
    matvals: jax.Array   # (nnz,) non-zero values
    indx: jax.Array      # (nnz,) column index of each value
    rowp: jax.Array      # (nrows+1,) row pointers
    shape: tuple[int, int]

    def tree_flatten(self):
        return (self.matvals, self.indx, self.rowp), self.shape

    @classmethod
    def tree_unflatten(cls, shape, children):
        return cls(*children, shape=shape)

    @property
    def nnz(self) -> int:
        return self.matvals.shape[0]

    def todense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.asarray(self.matvals).dtype)
        rowp = np.asarray(self.rowp)
        indx = np.asarray(self.indx)
        vals = np.asarray(self.matvals)
        for i in range(self.shape[0]):
            for p in range(rowp[i], rowp[i + 1]):
                out[i, indx[p]] += vals[p]
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ELL:
    """Padded fixed-width rows: values/cols are (nrows, width).

    Padding entries have value 0 and column 0 — harmless under multiply-add.
    """
    values: jax.Array    # (nrows, width)
    cols: jax.Array      # (nrows, width) int32
    shape: tuple[int, int]

    def tree_flatten(self):
        return (self.values, self.cols), self.shape

    @classmethod
    def tree_unflatten(cls, shape, children):
        return cls(*children, shape=shape)

    @property
    def width(self) -> int:
        return self.values.shape[1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DIA:
    """Diagonal storage: diags[d] holds the offsets[d]-th diagonal, aligned so
    that ``y += diags[d] * shift(x, -offsets[d])`` accumulates the SpMV."""
    diags: jax.Array             # (ndiags, n)
    offsets: tuple[int, ...]     # static python ints (drive trace-time loop)
    shape: tuple[int, int]

    def tree_flatten(self):
        return (self.diags,), (self.offsets, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], offsets=aux[0], shape=aux[1])


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------

def csr_from_dense(a: np.ndarray, dtype=None) -> CSR:
    a = np.asarray(a)
    if dtype is not None:
        a = a.astype(dtype)
    nrows, _ = a.shape
    rowp = [0]
    vals: list = []
    cols: list = []
    for i in range(nrows):
        (nz,) = np.nonzero(a[i])
        vals.extend(a[i, nz].tolist())
        cols.extend(nz.tolist())
        rowp.append(len(vals))
    return CSR(
        matvals=jnp.asarray(np.array(vals, dtype=a.dtype)),
        indx=jnp.asarray(np.array(cols, dtype=np.int32)),
        rowp=jnp.asarray(np.array(rowp, dtype=np.int32)),
        shape=a.shape,
    )


def ell_from_csr(csr: CSR, width: int | None = None, pad_to: int = 1) -> ELL:
    rowp = np.asarray(csr.rowp)
    indx = np.asarray(csr.indx)
    vals = np.asarray(csr.matvals)
    nrows = csr.shape[0]
    per_row = rowp[1:] - rowp[:-1]
    w = int(per_row.max()) if width is None else width
    w = max(1, -(-w // pad_to) * pad_to)
    values = np.zeros((nrows, w), dtype=vals.dtype)
    cols = np.zeros((nrows, w), dtype=np.int32)
    for i in range(nrows):
        k = per_row[i]
        if k > w:
            raise ValueError(f"row {i} has {k} nnz > ELL width {w}")
        values[i, :k] = vals[rowp[i]:rowp[i] + k]
        cols[i, :k] = indx[rowp[i]:rowp[i] + k]
    return ELL(values=jnp.asarray(values), cols=jnp.asarray(cols), shape=csr.shape)


def dia_from_dense(a: np.ndarray) -> DIA:
    a = np.asarray(a)
    n = a.shape[0]
    offsets = []
    diags = []
    for off in range(-(n - 1), n):
        d = np.diagonal(a, off)
        if np.any(d != 0):
            offsets.append(off)
            # align: row i uses x[i + off]; store padded to length n at index i
            full = np.zeros(n, dtype=a.dtype)
            if off >= 0:
                full[: n - off] = d
            else:
                full[-off:] = d
            diags.append(full)
    return DIA(diags=jnp.asarray(np.stack(diags)), offsets=tuple(offsets),
               shape=a.shape)


# ---------------------------------------------------------------------------
# paper input generators
# ---------------------------------------------------------------------------

# mod2as input list (paper Table 1): (n, fill %)
MOD2AS_TABLE1: Sequence[tuple[int, float]] = (
    (100, 3.50), (200, 3.75), (256, 5.0), (400, 4.38), (500, 5.00),
    (512, 4.00), (960, 4.50), (1000, 5.00), (1024, 5.50), (2000, 7.50),
    (4096, 3.50), (4992, 4.00), (5000, 4.00), (9984, 4.50), (10000, 5.00),
    (10240, 5.72),
)

# CG configs (paper Table 2): (n, bandwidth)
CG_TABLE2: Sequence[tuple[int, int]] = (
    (128, 3), (128, 31), (128, 63),
    (256, 3), (256, 31), (256, 63), (256, 127),
    (512, 3), (512, 31), (512, 63), (512, 127), (512, 255),
    (1024, 3), (1024, 31), (1024, 63), (1024, 127), (1024, 255), (1024, 511),
)


def random_sparse(n: int, fill_percent: float, seed: int = 0,
                  dtype=np.float64) -> np.ndarray:
    """Random square sparse matrix with the given fill ratio (mod2as inputs)."""
    rng = np.random.default_rng(seed)
    a = np.zeros((n, n), dtype=dtype)
    nnz = max(1, int(round(n * n * fill_percent / 100.0)))
    pos = rng.choice(n * n, size=nnz, replace=False)
    a.flat[pos] = rng.standard_normal(nnz)
    return a


def banded_spd(n: int, bw: int, seed: int = 0, dtype=np.float64) -> np.ndarray:
    """Symmetric positive-definite banded matrix with half-bandwidth ``bw``
    (CG inputs, paper Table 2).  Diagonal dominance guarantees SPD."""
    rng = np.random.default_rng(seed)
    a = np.zeros((n, n), dtype=dtype)
    for off in range(1, bw + 1):
        d = rng.standard_normal(n - off) * 0.5
        a[np.arange(n - off), np.arange(off, n)] = d
        a[np.arange(off, n), np.arange(n - off)] = d
    # strictly diagonally dominant diagonal
    a[np.arange(n), np.arange(n)] = np.abs(a).sum(axis=1) + 1.0
    return a


class Stencil3D(NamedTuple):
    """One 3-D stencil operator in the three layouts the kernels take."""
    dia: DIA
    ell: ELL
    csr: CSR


def stencil_3d(grid: int | tuple[int, int, int], *, points: int = 7,
               seed: int = 0, dtype=np.float32) -> Stencil3D:
    """Seeded variable-coefficient diffusion operator on an nx×ny×nz grid.

    Rows are grid points, x fastest (``i = x + nx*(y + ny*z)``).  Each
    stencil edge (the 6 face neighbours for ``points=7``, all 26 for
    ``points=27``) carries a conductance ``c = 1 + U[0, 0.5)`` drawn from
    ``seed``; ``A[i, j] = -c`` and ``A[i, i]`` sums the conductances of all
    its stencil edges, including those leaving the grid (Dirichlet), so A
    is symmetric, strictly diagonally dominant and positive definite.

    DIA, ELL and CSR are built from the offsets directly — never through a
    dense matrix — so chip-filling grids (128³ = 2,097,152 rows) are
    cheap.  ELL rows list their entries by ascending column, with
    out-of-grid neighbours as padding (value 0, column 0)."""
    nx, ny, nz = (grid,) * 3 if isinstance(grid, int) else grid
    if points == 7:
        steps = [(-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0), (0, 0, -1),
                 (0, 0, 1)]
    elif points == 27:
        steps = [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                 for dx in (-1, 0, 1) if (dx, dy, dz) != (0, 0, 0)]
    else:
        raise ValueError(f"points must be 7 or 27, got {points}")
    n = nx * ny * nz
    x, y, z = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                          indexing="ij")
    x, y, z = (a.transpose(2, 1, 0).reshape(-1) for a in (x, y, z))
    rng = np.random.default_rng(seed)

    def offset(s):
        return s[0] + nx * (s[1] + ny * s[2])

    def inside(s):
        return ((x + s[0] >= 0) & (x + s[0] < nx) & (y + s[1] >= 0)
                & (y + s[1] < ny) & (z + s[2] >= 0) & (z + s[2] < nz))

    # one conductance per undirected edge: drawn for the positive-offset
    # direction at its lower row, read back by the negative direction
    cond = {}
    for s in sorted(steps, key=offset):
        if offset(s) > 0:
            cond[s] = (1.0 + 0.5 * rng.random(n)).astype(dtype)
    diag = np.zeros(n, dtype)
    entries = {}                                   # offset -> (n,) values
    for s in steps:
        off = offset(s)
        up = s if off > 0 else tuple(-v for v in s)
        c = cond[up] if off > 0 else np.roll(cond[up], -off)
        diag += c                 # in-grid and Dirichlet (leaving) edges
        entries[off] = np.where(inside(s), -c, 0).astype(dtype)
    entries[0] = diag
    offsets = tuple(sorted(entries))
    dia_vals = np.stack([entries[o] for o in offsets])

    rows = np.arange(n)
    ell_cols = np.stack([rows + o for o in offsets], axis=1)
    ell_vals = dia_vals.T.copy()
    ell_cols = np.where(ell_vals != 0, ell_cols, 0).astype(np.int32)
    keep = ell_vals != 0
    rowp = np.concatenate([[0], np.cumsum(keep.sum(axis=1))]).astype(np.int32)
    return Stencil3D(
        dia=DIA(diags=jnp.asarray(dia_vals), offsets=offsets, shape=(n, n)),
        ell=ELL(values=jnp.asarray(ell_vals), cols=jnp.asarray(ell_cols),
                shape=(n, n)),
        csr=CSR(matvals=jnp.asarray(ell_vals[keep]),
                indx=jnp.asarray(ell_cols[keep]), rowp=jnp.asarray(rowp),
                shape=(n, n)))
