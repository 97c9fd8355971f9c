"""The paper's four kernel ports (numerics layer) against oracles +
the paper's *structural* claims (EXPERIMENTS.md §Paper-validation)."""
import numpy as np
import pytest

import repro.core as C
from repro.numerics import fft as nfft
from repro.numerics import matmul as mm
from repro.numerics import solvers, sparse, spmv


class TestMod2am:
    @pytest.mark.parametrize("n", [10, 20, 50, 64])
    def test_all_variants_match_oracle(self, n, rng):
        a = rng.standard_normal((n, n))
        b = rng.standard_normal((n, n))
        oracle = a.astype(np.float64) @ b.astype(np.float64)
        for fn in (mm.arbb_mxm0, mm.arbb_mxm1, mm.arbb_mxm2a, mm.arbb_mxm2b):
            out = fn(C.bind(a), C.bind(b)).read()
            np.testing.assert_allclose(out, oracle, rtol=2e-3, atol=2e-3)

    def test_mxm2b_unroll_u_invariance(self, rng):
        a = rng.standard_normal((32, 32))
        b = rng.standard_normal((32, 32))
        base = mm.arbb_mxm2b(C.bind(a), C.bind(b), u=8).read()
        for u in (1, 3, 5, 32):
            np.testing.assert_allclose(
                mm.arbb_mxm2b(C.bind(a), C.bind(b), u=u).read(), base,
                rtol=1e-4, atol=1e-4)


class TestMod2as:
    @pytest.mark.parametrize("n,fill", [(100, 3.5), (200, 3.75), (256, 5.0),
                                        (512, 4.0)])
    def test_spmv_table1_inputs(self, n, fill, rng):
        a = sparse.random_sparse(n, fill, seed=n)
        csr = sparse.csr_from_dense(a)
        x = rng.standard_normal(n)
        oracle = a @ x
        np.testing.assert_allclose(spmv.arbb_spmv1(csr, C.bind(x)).read(),
                                   oracle, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(spmv.arbb_spmv2(csr, C.bind(x)).read(),
                                   oracle, rtol=1e-3, atol=1e-3)

    def test_ell_and_dia_formats(self, rng):
        a = sparse.banded_spd(64, 3, seed=7)
        x = rng.standard_normal(64)
        oracle = a @ x
        csr = sparse.csr_from_dense(a)
        ell = sparse.ell_from_csr(csr)
        np.testing.assert_allclose(
            np.asarray(spmv.spmv_ell(ell, C.bind(x)).data), oracle,
            rtol=1e-3, atol=1e-3)
        dia = sparse.dia_from_dense(a)
        np.testing.assert_allclose(
            np.asarray(spmv.spmv_dia(dia, C.bind(x)).data), oracle,
            rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("points", [7, 27])
    def test_stencil_3d_matches_dense_construction(self, points):
        """At 8³ the generator's DIA equals dia_from_dense of its dense
        operator, and its ELL and CSR hold the same matrix."""
        op = sparse.stencil_3d(8, points=points, seed=3)
        a = op.csr.todense()
        assert op.csr.shape == (512, 512)
        np.testing.assert_array_equal(a, a.T)
        want = sparse.dia_from_dense(a)
        assert op.dia.offsets == want.offsets
        np.testing.assert_array_equal(np.asarray(op.dia.diags),
                                      np.asarray(want.diags))
        vals, cols = np.asarray(op.ell.values), np.asarray(op.ell.cols)
        dense_ell = np.zeros_like(a)
        np.add.at(dense_ell, (np.repeat(np.arange(512), vals.shape[1]),
                              cols.ravel()), vals.ravel())
        np.testing.assert_array_equal(dense_ell, a)
        assert np.linalg.eigvalsh(a.astype(np.float64)).min() > 0


class TestMod2f:
    @pytest.mark.parametrize("n", [256, 1024, 4096])
    def test_split_stream_matches_fft(self, n):
        rng = np.random.default_rng(n)
        z = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
            np.complex64)
        out = nfft.split_stream_fft(C.bind(z)).read()
        np.testing.assert_allclose(out, np.fft.fft(z), rtol=1e-2,
                                   atol=1e-3 * n)

    def test_structural_claim_no_gather_in_stage_loop(self):
        """Paper §3.3: split-stream needs no reordering after the initial
        tangle — the captured stage-loop IR must be gather/scatter-free."""
        n = 64
        tw = nfft.split_stream_twiddles(n)
        cl = C.capture(nfft.arbb_fft,
                       C.Dense.zeros(n, dtype=np.complex64),
                       C.bind(tw.astype(np.complex64)))
        assert cl.gather_free(), cl.op_counts()

    def test_stockham_and_naive_agree(self):
        n = 512
        rng = np.random.default_rng(3)
        z = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
            np.complex64)
        want = np.fft.fft(z)
        np.testing.assert_allclose(nfft.stockham_fft(C.bind(z)).read(), want,
                                   rtol=1e-2, atol=1e-3 * n)
        np.testing.assert_allclose(nfft.naive_radix2_fft(C.bind(z)).read(),
                                   want, rtol=1e-2, atol=1e-3 * n)

    def test_dft_ref_tiny(self):
        z = np.asarray([1, 2j, -1, -2j], np.complex64)
        np.testing.assert_allclose(nfft.dft_ref(C.bind(z)).read(),
                                   np.fft.fft(z), rtol=1e-5, atol=1e-5)


class TestCG:
    # the paper's Table 2: (n, bw) configurations
    TABLE2 = [(128, 3), (128, 31), (128, 63), (256, 3), (256, 31), (256, 63),
              (256, 127), (512, 3), (512, 31), (512, 63), (512, 127),
              (512, 255), (1024, 3), (1024, 31), (1024, 63), (1024, 127),
              (1024, 255), (1024, 511)]

    @pytest.mark.parametrize("n,bw", TABLE2[:8])
    def test_cg_converges_paper_configs(self, n, bw):
        rng = np.random.default_rng(n + bw)
        a = sparse.banded_spd(n, bw, seed=n + bw)
        b = rng.standard_normal(n).astype(np.float32)
        res = solvers.cg_solve(sparse.csr_from_dense(a), C.bind(b),
                               stop=1e-12, max_iters=4 * n)
        x = res.x.read()
        rel = np.linalg.norm(a @ x - b) / np.linalg.norm(b)
        assert rel < 1e-3, (n, bw, rel)

    def test_cg_spmv_backends_agree(self):
        n, bw = 128, 7
        a = sparse.banded_spd(n, bw, seed=11)
        rng = np.random.default_rng(1)
        b = rng.standard_normal(n).astype(np.float32)
        xs = {}
        for backend in ("spmv1", "spmv2", "dia"):
            res = solvers.cg_solve(sparse.csr_from_dense(a) if backend != "dia"
                                   else sparse.dia_from_dense(a),
                                   C.bind(b), stop=1e-12, max_iters=600,
                                   backend=backend)
            xs[backend] = res.x.read()
        np.testing.assert_allclose(xs["spmv1"], xs["spmv2"], rtol=1e-3,
                                   atol=1e-3)
        np.testing.assert_allclose(xs["spmv1"], xs["dia"], rtol=1e-3,
                                   atol=1e-3)

    def test_jacobi_gauss_seidel(self):
        n = 64
        a = sparse.banded_spd(n, 2, seed=5)
        rng = np.random.default_rng(2)
        b = rng.standard_normal(n).astype(np.float32)
        xj = solvers.jacobi_solve(a, C.bind(b), iters=4000).read()
        assert np.linalg.norm(a @ xj - b) / np.linalg.norm(b) < 1e-2
        xg = solvers.gauss_seidel_solve(a, C.bind(b), iters=1500).read()
        assert np.linalg.norm(a @ xg - b) / np.linalg.norm(b) < 1e-2
