"""Work counts of each traffic mix, from shapes, against hand counts.

SpMV: (ndiags + 2) * 4 B per row (every stored diagonal entry, x, y) and
2 FLOPs per stored non-zero.  CG iteration: the least traffic of textbook
CG, ndiags * 4 B per row of diagonals plus 9 vector accesses of 4 B per row
(x, p, Ap 2 each, r 3), less the accesses of the vectors that fit in 3/4 of
the chip's VMEM, most accessed first; 2 FLOPs per stored non-zero plus 10
per row."""
import dataclasses
import json
import os

import jax
import pytest

from bench import peaks
from bench.harness import ROOT, Work, load_module

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
    """An iteration once counted 72 B/row, every vector from HBM plus the
    SpMV's own x and y; the least is 64 from HBM, and 52 on a v5e, whose
    VMEM holds r's 64 MiB."""
    op = gen.Operator(config("stencil7_256"))
    n = 256 ** 3
    w = cg.work(op, 700)
    assert w.hbm_bytes == 700 * (7 + 9) * 4 * n
    assert w.flops == 700 * (2 * (7 * n - 6 * 256 ** 2) + 10 * n)
    v5e = peaks.lookup("TPU v5 lite")
    assert peaks.resident_bytes(w, v5e) == 700 * 3 * 4 * n
    assert peaks.least_seconds(w, v5e) == pytest.approx(700 * 52 * n / 819e9)


MIB = 2 ** 20


@pytest.mark.parametrize("rows,held", [
    (16 * MIB // 4, {"r": 3, "x": 2, "p": 2, "Ap": 2}),   # all four fit
    (64 * MIB // 4, {"r": 3}),                  # 256^3: r, then 32 MiB left
    (96 * MIB // 4, {"r": 3}),                  # exactly 3/4 of VMEM
    (96 * MIB // 4 + 1, {}),                    # one row over the line
    (360 ** 3, {}),                             # HPCG's shard, 186.6 MB
])
def test_vmem_holds_the_most_accessed_vectors_that_fit(rows, held):
    w = cg.iterations_work(rows, nnz=7 * rows, ndiags=7, iterations=10)
    assert w.hbm_bytes == 10 * (7 + 9) * 4 * rows
    assert peaks.resident_bytes(w, peaks.lookup("TPU v5 lite")) == \
        10 * sum(held.values()) * 4 * rows


def test_a_kind_with_no_vmem_size_raises():
    """A chip with no published VMEM size gives no least time for work that
    lists vectors, rather than a default; work that lists none needs no
    VMEM size."""
    bare = dataclasses.replace(peaks.lookup("TPU v5 lite"), vmem_bytes=None)
    with pytest.raises(ValueError, match="no published VMEM size"):
        peaks.least_seconds(cg.iterations_work(1024, 7168, 7, 1), bare)
    assert peaks.least_seconds(Work(flops=0, hbm_bytes=819e9), bare) == 1.0


@pytest.mark.parametrize("control", [False, True])
def test_cg_count_is_the_shapes_whichever_path_runs(control):
    """The program and the bfloat16 control, run at a small grid, are
    counted alike: from the operator's shapes and the iterations alone."""
    cfg = dict(config("stencil7_256"), grid=[16, 16, 4])
    traffic = {"rtol": 1e-6, "max_iters": 3000, "pool": 1,
               "control_dtype": "bfloat16"}
    cell = cg.Cell(cfg, traffic, 2 ** 31 + 7, jax.devices()[:1],
                   control=control)
    stats = cell.stats([cell.stat_of(cell.call(0))])
    w = cell.work(stats)
    assert w == cg.work(gen.Operator(cfg), stats["iterations"])
    assert w.hbm_bytes == stats["iterations"] * (7 + 9) * 4 * 16 * 16 * 4


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
