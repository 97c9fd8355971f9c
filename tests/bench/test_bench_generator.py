"""The seeded stencil operators made on the device (bench/generators/
stencil.py), at tiny grids, against ``numerics.sparse.stencil_3d``'s
structure, and the plain reference SpMV and CG."""
import numpy as np
import pytest

from bench.harness import ROOT, load_module

gen = load_module(ROOT, "generators", "stencil")
GRID = (6, 5, 4)
SEED = 2 ** 31 + 7


def dense(diags, offs):
    diags = np.asarray(diags, np.float64)
    n = diags.shape[1]
    a = np.zeros((n, n))
    for d, off in enumerate(offs):
        for i in range(n):
            if 0 <= i + off < n:
                a[i, i + off] = diags[d, i]
    return a


@pytest.mark.parametrize("points", [7, 27])
def test_operator_is_symmetric_dominant_and_matches_stencil_3d(points):
    from repro.numerics.sparse import stencil_3d

    diags = gen.make(SEED, GRID, points)
    offs = gen.offsets(GRID, points)
    ref = stencil_3d(GRID, points=points, seed=0)
    assert offs == ref.dia.offsets
    assert diags.shape == ref.dia.diags.shape
    # the same zero pattern: out-of-grid neighbours are 0 in both
    np.testing.assert_array_equal(np.asarray(diags) != 0,
                                  np.asarray(ref.dia.diags) != 0)
    a = dense(diags, offs)
    np.testing.assert_array_equal(a, a.T)
    # weakly dominant inside (the diagonal is the sum of the row's
    # conductances, rounded in f32), strictly at the Dirichlet boundary
    off_sum = np.abs(a).sum(axis=1) - np.abs(np.diag(a))
    assert np.all(np.diag(a) >= off_sum * (1 - 1e-6))
    boundary = np.count_nonzero(a, axis=1) < len(offs)
    assert boundary.any()
    assert np.all(np.diag(a)[boundary] > off_sum[boundary] + 0.5)
    assert np.all(np.linalg.eigvalsh(a) > 0)
    assert gen.nnz(GRID, points) == np.count_nonzero(a)
    c = -a[a < 0]
    assert c.min() >= 1.0 and c.max() < 1.5


def test_numpy_mirror_agrees_with_the_device():
    rows = np.arange(np.prod(GRID), dtype=np.int32)
    keys = gen.direction_keys(SEED, GRID, 27)
    want = gen.diagonals(rows, keys, GRID, 27, xp=np)
    np.testing.assert_array_equal(np.asarray(gen.make(SEED, GRID, 27)), want)


def test_seeds_differ_beyond_32_bits_and_repeat():
    a = np.asarray(gen.make(SEED, GRID, 7))
    np.testing.assert_array_equal(a, np.asarray(gen.make(SEED, GRID, 7)))
    assert not np.array_equal(a, np.asarray(gen.make(SEED + 2 ** 32,
                                                     GRID, 7)))
    v = np.asarray(gen.vectors(SEED, 4096, 2)[0])
    assert abs(v.mean()) < 0.1 and abs(v.std() - 1) < 0.05


def test_spmv_matches_the_dense_product():
    offs = gen.offsets(GRID, 27)
    diags = gen.make(SEED, GRID, 27)
    x = gen.vectors(SEED, diags.shape[1], 1)[0]
    np.testing.assert_allclose(np.asarray(gen.spmv(diags, offs, x)),
                               dense(diags, offs) @ np.asarray(x),
                               rtol=1e-5, atol=1e-5)


def test_reference_cg_solves():
    offs = gen.offsets(GRID, 7)
    diags = gen.make(SEED, GRID, 7)
    b = gen.vectors(SEED, diags.shape[1], 1)[0]
    x, k = gen.cg(diags, b, spmv=lambda d, v: gen.spmv(d, offs, v),
                  rtol=1e-6, max_iters=500)
    a = dense(diags, offs)
    res = np.linalg.norm(np.asarray(b) - a @ np.asarray(x, np.float64))
    assert res / np.linalg.norm(np.asarray(b)) < 1e-5
    assert 0 < int(k) < 500


def test_operator_refuses_more_than_one_chip():
    with pytest.raises(ValueError, match="one chip"):
        gen.Operator({"grid": [8, 8, 16], "points": 7,
                      "process_grid": [1, 1, 4]})
