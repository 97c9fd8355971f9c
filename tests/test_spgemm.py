"""SpGEMM on the blocked plane (DESIGN.md §15): the two-phase BSR×BSR op,
its symbolic pattern, the Cannon-style mesh variant, and the
dispatcher-propagated output sharding.

Contracts under test:
  * numerics — ``sparse.spgemm`` matches the dense product on every format
    pairing (f32, 1e-5) and every chip plane, including empty / diagonal /
    banded patterns;
  * symbolic — the computed block pattern equals the boolean block-matmul
    reference exactly, and the realised pair count never exceeds the
    stats-derived :meth:`SparseStats.product_block_bound`;
  * stats — the new per-axis live-block counts round-trip what the matrix
    actually contains (satellite: stats fields);
  * converters — ``block_pattern`` is the one shared pattern scan:
    ``bsr_from_csr`` and ``bsr_from_dense`` produce identical containers
    (satellite: converter dedup);
  * mesh — ``mesh_spgemm`` is selected under O3/O4, matches chip on
    mesh8/mesh222, degrades to chip without a mesh or on indivisible
    grids, and honours explicit ``variant=`` pins;
  * out-sharding — the dispatcher attaches the decided ``NamedSharding``
    to the product, it equals the values' actual sharding (so a chained
    op consumes without a reshard), and ``obs.explain`` surfaces it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro import sparse as S
from repro.core import ExecLevel, registry, unwrap, use_level
from repro.numerics.sparse import banded_spd


def _blocked(n=128, block=8, frac=0.3, seed=2):
    rng = np.random.default_rng(seed)
    nb = n // block
    occ = rng.random((nb, nb)) < frac
    d = rng.standard_normal((n, n)).astype(np.float32)
    return np.where(np.kron(occ, np.ones((block, block), bool)), d, 0.0) \
        .astype(np.float32)


def _banded(n=128, bw=7, seed=1):
    return banded_spd(n, bw, seed=seed).astype(np.float32)


def _chip_variant():
    """The chip SpGEMM variant the CPU selects: 'bsr_xla' by default,
    'bsr_interpret' under an interpret-plane request (./test.sh)."""
    return "bsr_" + registry.resolve_backend()


def _block_occupancy(a, bs):
    n, m = a.shape
    return (a.reshape(n // bs, bs, m // bs, bs) != 0).any(axis=(1, 3))


# ---------------------------------------------------------------------------
# chip numerics: every format pairing, every plane, edge patterns
# ---------------------------------------------------------------------------

class TestChipSpgemm:
    @pytest.mark.parametrize("fmt_a,fmt_b", [
        ("bsr", "bsr"), ("bsr", "csr"), ("csr", "bsr"),
        ("csr", "csr"), ("ell", "dia"), ("dia", "bsr")])
    def test_format_pairings_match_dense(self, fmt_a, fmt_b):
        A, B = _blocked(seed=2), _banded()
        a = S.matrix(A, format=fmt_a)
        b = S.matrix(B, format=fmt_b)
        C = S.spgemm(a, b)
        assert isinstance(C, S.BSR)
        np.testing.assert_allclose(C.todense(), A @ B, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("variant", ["bsr_interpret", "bsr_xla", "dense"])
    def test_planes_match_dense(self, variant):
        A, B = _blocked(seed=3), _blocked(seed=4)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        C = S.spgemm(a, b, variant=variant)
        np.testing.assert_allclose(C.todense(), A @ B, rtol=1e-5, atol=1e-5)

    def test_empty_operand(self):
        z = S.bsr_from_dense(np.zeros((64, 64), np.float32))
        b = S.bsr_from_dense(_blocked(64))
        C = S.spgemm(z, b)
        assert C.nblocks == 0
        np.testing.assert_array_equal(C.todense(), np.zeros((64, 64)))

    def test_block_diagonal_stays_diagonal(self):
        rng = np.random.default_rng(5)
        n, bs = 64, 8
        A = np.zeros((n, n), np.float32)
        for i in range(n // bs):
            A[i * bs:(i + 1) * bs, i * bs:(i + 1) * bs] = \
                rng.standard_normal((bs, bs))
        a = S.bsr_from_dense(A, block=bs)
        C = S.spgemm(a, a)
        assert C.nblocks == n // bs          # pattern: still diagonal
        np.testing.assert_allclose(C.todense(), A @ A, rtol=1e-5, atol=1e-5)

    def test_banded_times_banded(self):
        A = _banded(128, 7, seed=6)
        B = _banded(128, 3, seed=7)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        C = S.spgemm(a, b)
        np.testing.assert_allclose(C.todense(), A @ B, rtol=1e-5, atol=1e-4)

    def test_chip_selection_and_pin(self):
        a = S.bsr_from_dense(_blocked(64))
        b = S.bsr_from_dense(_blocked(64, seed=8))
        assert registry.select("spgemm", a, b).name == _chip_variant()
        assert registry.select("spgemm", a, b,
                               variant="dense").name == "dense"
        with registry.use_backend("interpret"):
            assert registry.select("spgemm", a, b).name == "bsr_interpret"


# ---------------------------------------------------------------------------
# symbolic phase: pattern exactness + the stats-derived bound
# ---------------------------------------------------------------------------

class TestSymbolic:
    def test_pattern_matches_boolean_block_matmul(self):
        A, B = _blocked(seed=10), _blocked(seed=11, frac=0.4)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        plan = S.spgemm_symbolic(a, b)
        occ = (_block_occupancy(A, 8).astype(np.int64)
               @ _block_occupancy(B, 8).astype(np.int64)) > 0
        cols_ref, rowp_ref = S.block_pattern(occ)
        np.testing.assert_array_equal(plan.c_cols, cols_ref)
        np.testing.assert_array_equal(plan.c_rowp, rowp_ref)

    def test_pair_list_reconstructs_product(self):
        A, B = _blocked(64, seed=12), _blocked(64, seed=13)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        plan = S.spgemm_symbolic(a, b)
        # accumulate the pairs by hand: the numeric phase's contract
        av = np.asarray(a.values)
        bv = np.asarray(b.values)
        vals = np.zeros((plan.nc, 8, 8), np.float32)
        for p, q, r in zip(plan.pair_p, plan.pair_q, plan.pair_r):
            vals[r] += av[p] @ bv[q]
        C = S.spgemm(a, b)
        np.testing.assert_allclose(np.asarray(C.values), vals,
                                   rtol=1e-5, atol=1e-5)

    def test_pair_count_within_stats_bound(self):
        A, B = _blocked(seed=14), _blocked(seed=15)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        plan = S.spgemm_symbolic(a, b)
        bound = a.stats.product_block_bound(b.stats)
        assert 0 < plan.npairs <= bound
        # dense operands: the bound is exactly the pair count (no overlap
        # uncertainty in the product count itself)
        assert plan.npairs == bound

    def test_mismatched_dims_raise(self):
        a = S.bsr_from_dense(_blocked(64))
        b = S.bsr_from_dense(_blocked(128))
        with pytest.raises(ValueError, match="inner dims"):
            S.spgemm_symbolic(a, b)


# ---------------------------------------------------------------------------
# satellite: SparseStats per-axis live-block counts
# ---------------------------------------------------------------------------

class TestStatsFields:
    def test_counts_round_trip(self):
        A = _blocked(seed=20)
        st = S.sparse_stats(A, block=8)
        occ = _block_occupancy(A, 8)
        np.testing.assert_array_equal(st.block_row_counts,
                                      occ.sum(axis=1))
        np.testing.assert_array_equal(st.block_col_counts,
                                      occ.sum(axis=0))
        assert sum(st.block_row_counts) == st.nblocks
        assert sum(st.block_col_counts) == st.nblocks

    def test_empty_matrix_counts(self):
        st = S.sparse_stats(np.zeros((32, 32), np.float32), block=8)
        assert st.block_row_counts == (0, 0, 0, 0)
        assert st.block_col_counts == (0, 0, 0, 0)
        assert st.nblocks == 0

    def test_product_bound_formula(self):
        A, B = _blocked(64, seed=21), _blocked(64, seed=22)
        sa = S.sparse_stats(A, block=8)
        sb = S.sparse_stats(B, block=8)
        want = int(np.dot(sa.block_col_counts, sb.block_row_counts))
        assert sa.product_block_bound(sb) == want

    def test_block_mismatch_raises(self):
        sa = S.sparse_stats(_blocked(64), block=8)
        sb = S.sparse_stats(_blocked(64), block=4)
        with pytest.raises(ValueError, match="block mismatch"):
            sa.product_block_bound(sb)


# ---------------------------------------------------------------------------
# satellite: one shared pattern scan for all converters
# ---------------------------------------------------------------------------

class TestBlockPattern:
    def test_block_pattern_scan(self):
        occ = np.array([[1, 0, 1], [0, 0, 0], [0, 1, 1]], bool)
        cols, rowp = S.block_pattern(occ)
        np.testing.assert_array_equal(cols, [0, 2, 1, 2])
        np.testing.assert_array_equal(rowp, [0, 2, 2, 4])
        assert cols.dtype == np.int32 and rowp.dtype == np.int32

    def test_csr_and_dense_paths_agree(self):
        A = _blocked(seed=23)
        csr = S.matrix(A, format="csr")
        via_csr = S.bsr_from_csr(csr)
        via_dense = S.bsr_from_dense(A)
        np.testing.assert_array_equal(np.asarray(via_csr.cols),
                                      np.asarray(via_dense.cols))
        np.testing.assert_array_equal(np.asarray(via_csr.rowp),
                                      np.asarray(via_dense.rowp))
        np.testing.assert_allclose(np.asarray(via_csr.values),
                                   np.asarray(via_dense.values), rtol=1e-6)


# ---------------------------------------------------------------------------
# mesh: Cannon-style variant — selection, parity, degradation, sharding
# ---------------------------------------------------------------------------

class TestMeshSpgemm:
    def _operands(self, n=128, seed=30):
        A = _blocked(n, seed=seed, frac=0.35)
        B = _blocked(n, seed=seed + 1, frac=0.35)
        return A, B, S.bsr_from_dense(A), S.bsr_from_dense(B)

    def test_mesh8_selected_and_matches_chip(self, mesh8):
        A, B, a, b = self._operands()
        chip = S.spgemm(a, b, variant="bsr_xla")
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("spgemm", a, b).name == "mesh_spgemm"
            C = S.spgemm(a, b)
        np.testing.assert_allclose(C.todense(), chip.todense(),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(C.todense(), A @ B, rtol=1e-5, atol=1e-4)

    def test_mesh222_hierarchical_matches_chip(self, mesh222):
        A, B, a, b = self._operands(seed=31)
        with use_level(ExecLevel.O4, mesh222):
            assert registry.select("spgemm", a, b).name == "mesh_spgemm"
            C = S.spgemm(a, b)
        np.testing.assert_allclose(C.todense(), A @ B, rtol=1e-5, atol=1e-4)

    def test_no_mesh_degrades_to_chip(self):
        _, _, a, b = self._operands()
        assert registry.select("spgemm", a, b).name == _chip_variant()

    def test_indivisible_rows_degrade_to_chip(self, mesh8):
        # 72 rows / block 8 = 9 block-rows: not divisible by the 8-wide
        # row partition — mesh accepts() refuses, chip runs
        A, B = _blocked(72, seed=32, frac=0.5), _blocked(72, seed=33,
                                                         frac=0.5)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("spgemm", a, b).name == _chip_variant()
            C = S.spgemm(a, b)
        assert C.out_sharding is None
        np.testing.assert_allclose(C.todense(), A @ B, rtol=1e-5, atol=1e-4)

    def test_explicit_pin_beats_mesh(self, mesh8):
        A, B, a, b = self._operands()
        with use_level(ExecLevel.O3, mesh8):
            C = S.spgemm(a, b, variant="dense")
        assert C.out_sharding is None        # chip variant declares nothing
        np.testing.assert_allclose(C.todense(), A @ B, rtol=1e-5, atol=1e-4)


class TestOutSharding:
    def test_decided_sharding_attached_and_real(self, mesh8):
        A = _blocked(seed=40, frac=0.35)
        B = _blocked(seed=41, frac=0.35)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        with use_level(ExecLevel.O3, mesh8):
            C = S.spgemm(a, b)
        assert C.out_sharding is not None
        # the declaration IS the layout the values came back in — no
        # reshard between producer and consumer
        assert C.values.sharding == C.out_sharding
        spec = C.out_sharding.spec
        assert spec[0] == "data"

    def test_mesh222_shards_over_pod_and_data(self, mesh222):
        A = _blocked(seed=42, frac=0.35)
        B = _blocked(seed=43, frac=0.35)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        with use_level(ExecLevel.O4, mesh222):
            C = S.spgemm(a, b)
        assert C.values.sharding == C.out_sharding
        assert C.out_sharding.spec[0] == ("pod", "data")

    def test_chained_consumption_without_reshard(self, mesh8):
        A = _blocked(seed=44, frac=0.35)
        B = _blocked(seed=45, frac=0.35)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        x = np.random.default_rng(46).standard_normal((128, 16)) \
            .astype(np.float32)
        with use_level(ExecLevel.O3, mesh8):
            C = S.spgemm(a, b)
            before = C.values.sharding
            # chained spgemm re-enters the mesh variant on the sharded
            # product directly (the symbolic phase skips the pad blocks)
            D = S.spgemm(C, b)
            y = S.spmm(C, jnp.asarray(x))
        assert C.values.sharding == before           # untouched by chaining
        assert D.out_sharding is not None
        np.testing.assert_allclose(D.todense(), (A @ B) @ B,
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(np.asarray(unwrap(y)), (A @ B) @ x,
                                   rtol=1e-5, atol=1e-3)

    def test_explain_reports_decided_sharding(self, mesh8):
        A = _blocked(seed=47, frac=0.35)
        B = _blocked(seed=48, frac=0.35)
        a, b = S.bsr_from_dense(A), S.bsr_from_dense(B)
        with use_level(ExecLevel.O3, mesh8):
            rows = obs.explain("spgemm", a, b)
            text = obs.explain_str(rows)
        sel = [r for r in rows if r["selected"]]
        assert sel and sel[0]["variant"] == "mesh_spgemm"
        assert sel[0]["out_sharding"] and "data" in sel[0]["out_sharding"]
        # chip candidates declare no layout
        assert all(r["out_sharding"] is None for r in rows
                   if r["variant"] != "mesh_spgemm")
        assert "decided out_sharding:" in text

    def test_explain_off_mesh_has_no_sharding(self):
        a = S.bsr_from_dense(_blocked(64, seed=49))
        b = S.bsr_from_dense(_blocked(64, seed=50))
        rows = obs.explain("spgemm", a, b)
        assert all(r["out_sharding"] is None for r in rows)
        assert "decided out_sharding" not in obs.explain_str(rows)


# ---------------------------------------------------------------------------
# cost-model fingerprints: BSR operands key the calibration per density
# ---------------------------------------------------------------------------

class TestCostDims:
    def test_bsr_cost_dims(self):
        a = S.bsr_from_dense(_blocked(64, seed=51))
        d = a.cost_dims()
        assert d["block"] == 8 and d["nnzb"] == a.nblocks

    def test_signature_fingerprints_positional_bsr(self):
        from repro.core import costmodel
        a = S.bsr_from_dense(_blocked(64, seed=52))
        b = S.bsr_from_dense(_blocked(64, seed=53))
        dims = costmodel.signature((a, b))
        assert dims["a0.block"] == 8 and dims["a1.block"] == 8
        assert dims["a0.nnzb"] == a.nblocks
        assert dims["a1.nnzb"] == b.nblocks
        # shape axes still contribute alongside the fingerprint
        assert dims["a0.0"] == 64 and dims["a1.1"] == 64
