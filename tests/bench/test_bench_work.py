"""Work counts of each traffic mix, from shapes, against hand counts.

SpMV: (ndiags + 2) * 4 B per row (every stored diagonal entry, x, y) and
2 FLOPs per stored non-zero.  CG iteration: one SpMV plus the minimum
vector passes, 9 * 4 B per row, and 10 FLOPs per row."""
import json
import os

import pytest

from bench import peaks
from bench.harness import ROOT, load_module

gen = load_module(ROOT, "generators", "stencil")
cg = load_module(ROOT, "entries", "cg")
spmv_dia = load_module(ROOT, "entries", "spmv_dia")


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_stencil7_256_spmv_moves_36_bytes_per_row():
    op = gen.Operator(config("stencil7_256"))
    n = 256 ** 3
    w = spmv_dia.work(op)
    assert w.hbm_bytes == 9 * 4 * n == 603_979_776
    # 7 n minus the 6 faces' missing neighbours: 6 * 256^2 entries
    assert w.flops == 2 * (7 * n - 6 * 256 ** 2)
    # 604 MB at 819 GB/s: 0.737 ms, the HBM term binds
    assert peaks.least_seconds(w, peaks.lookup("TPU v5 lite")) == \
        pytest.approx(603_979_776 / 819e9)


def test_stencil7_256_cg_iteration_is_72_bytes_per_row():
    op = gen.Operator(config("stencil7_256"))
    n = 256 ** 3
    w = cg.work(op, 700)
    assert w.hbm_bytes == 700 * 72 * n
    assert w.flops == 700 * (2 * (7 * n - 6 * 256 ** 2) + 10 * n)


@pytest.mark.parametrize("grid,points", [((4, 3, 2), 7), ((4, 3, 2), 27),
                                         ((256, 256, 256), 7)])
def test_nnz_counts_in_grid_neighbours(grid, points):
    nx, ny, nz = grid
    want = nx * ny * nz
    for dx, dy, dz in gen.steps(points):
        want += (nx - abs(dx)) * (ny - abs(dy)) * (nz - abs(dz))
    assert gen.nnz(grid, points) == want
    if points == 27 and grid == (4, 3, 2):
        # by hand: each point's in-grid neighbours in a 4x3x2 box
        per_axis = lambda m: [(2 if 0 < i < m - 1 else 1) + 1  # noqa: E731
                              for i in range(m)]
        total = sum(a * b * c for a in per_axis(nx) for b in per_axis(ny)
                    for c in per_axis(nz))
        assert gen.nnz(grid, points) == total


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup("TPU v9 imaginary")
