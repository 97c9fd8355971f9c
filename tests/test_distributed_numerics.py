"""The distributed numerics plane (DESIGN.md §7-§8): scope-aware selection
and the shard_map formulations of the four paper kernels on 8 fake devices.

Contracts under test:
  * selection — mesh-scoped variants win under use_level(O3) with an active
    mesh, chip variants win without one, explicit ``variant=`` pins either,
    and non-divisible shapes degrade back to chip;
  * numerics — every mesh formulation (SpMV × 3 layouts, psum_scatter
    matmul, transpose FFT, psum CG) matches its single-chip counterpart;
  * hierarchy (O4, the (2,2,2) mesh) — the collectives plane emits
    reduce-scatter-intra-pod / all-reduce-inter-pod schedules, the 2-D
    (data, model) matmul and pod-aware CG select automatically with no
    program-text change, degrade to the 1-D forms on O3 and to chip with
    no mesh, and match chip numerics.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.core as C
from repro.core import ExecLevel, registry, use_level
from repro.distributed import collectives
from repro.kernels import ops
from repro.numerics import solvers, sparse

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8 forced host devices")


def _banded(n=256, bw=31, seed=3):
    a = sparse.banded_spd(n, bw, seed=seed)
    rng = np.random.default_rng(seed)
    x = C.bind(rng.standard_normal(n).astype(np.float32))
    return a, x


# ---------------------------------------------------------------------------
# scope-aware selection
# ---------------------------------------------------------------------------

class TestScopeSelection:
    def test_mesh_variant_under_mesh_chip_without(self, mesh8):
        a, x = _banded()
        ell = sparse.ell_from_csr(sparse.csr_from_dense(a))
        assert registry.select("solver_spmv", ell, x).name == "ell"
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("solver_spmv", ell, x).name == "mesh_ell"
        # context restored: chip again
        assert registry.select("solver_spmv", ell, x).name == "ell"

    def test_all_layouts_route_to_their_mesh_variant(self, mesh8):
        a, x = _banded()
        csr = sparse.csr_from_dense(a)
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("solver_spmv", csr, x).name == "mesh_csr"
            assert registry.select(
                "solver_spmv", sparse.ell_from_csr(csr), x).name == "mesh_ell"
            assert registry.select(
                "solver_spmv", sparse.dia_from_dense(a), x).name == "mesh_dia"

    def test_explicit_variant_pins_chip_under_mesh(self, mesh8):
        a, x = _banded()
        dia = sparse.dia_from_dense(a)
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("solver_spmv", dia, x,
                                   variant="dia").name == "dia"
            assert registry.select("solver_spmv", dia, x,
                                   variant="mesh_dia").name == "mesh_dia"
            y_chip = registry.dispatch("solver_spmv", dia, x, variant="dia")
            y_mesh = registry.dispatch("solver_spmv", dia, x,
                                       variant="mesh_dia")
        np.testing.assert_allclose(y_chip.read(), y_mesh.read(),
                                   rtol=1e-5, atol=1e-5)

    def test_indivisible_rows_degrade_to_chip(self, mesh8):
        # 100 rows % 8 devices != 0 -> the mesh variant's accepts() fails
        # and selection falls through to the chip formulation
        a = sparse.banded_spd(100, 3, seed=1)
        x = C.bind(np.random.default_rng(1).standard_normal(100)
                   .astype(np.float32))
        ell = sparse.ell_from_csr(sparse.csr_from_dense(a))
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("solver_spmv", ell, x).name == "ell"

    def test_matmul_and_fft_scope_selection(self, mesh8):
        a = jnp.ones((64, 64), jnp.float32)
        z = jnp.ones(256, jnp.complex64)
        assert registry.select("matmul", a, a).scope == "chip"
        assert registry.select("fft", z).scope == "chip"
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("matmul", a, a).name == "mesh_psum"
            assert registry.select("fft", z).name == "mesh_transpose"
            # shapes the mesh can't host degrade gracefully
            odd = jnp.ones((30, 30), jnp.float32)
            assert registry.select("matmul", odd, odd).scope == "chip"
            assert registry.select("fft", jnp.ones(40, jnp.complex64)
                                   ).scope == "chip"

    def test_mesh_scope_outranks_requested_plane(self, mesh8):
        """Scope beats the plane request: even with 'interpret' explicitly
        requested, the sharded formulation wins under a mesh."""
        a = jnp.ones((64, 64), jnp.float32)
        with use_level(ExecLevel.O3, mesh8), registry.use_backend("interpret"):
            assert registry.select("matmul", a, a).name == "mesh_psum"


# ---------------------------------------------------------------------------
# numerics: mesh == chip
# ---------------------------------------------------------------------------

class TestMeshNumerics:
    def test_mesh_spmv_matches_chip_all_layouts(self, mesh8):
        a, x = _banded()
        csr = sparse.csr_from_dense(a)
        mats = [csr, sparse.ell_from_csr(csr), sparse.dia_from_dense(a)]
        want = a.astype(np.float32) @ x.read()
        for m in mats:
            with use_level(ExecLevel.O3, mesh8):
                got = registry.dispatch("solver_spmv", m, x).read()
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_mesh_matmul_matches_chip(self, mesh8, rng):
        a = jnp.asarray(rng.standard_normal((64, 128)))
        b = jnp.asarray(rng.standard_normal((128, 96)))
        want = np.asarray(ops.matmul(a, b))
        with use_level(ExecLevel.O3, mesh8):
            got = np.asarray(ops.matmul(a, b))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_mesh_fft_matches_reference(self, mesh8, rng):
        z = jnp.asarray(rng.standard_normal(512)
                        + 1j * rng.standard_normal(512), jnp.complex64)
        want = np.fft.fft(np.asarray(z))
        with use_level(ExecLevel.O3, mesh8):
            got = np.asarray(ops.fft(z))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)

    @pytest.mark.parametrize("n,bw", [(128, 3), (256, 31), (512, 63)])
    def test_mesh_cg_matches_chip_on_table2(self, mesh8, n, bw):
        """Sharded CG == single-chip CG to 1e-5 on paper Table-2 systems."""
        a = sparse.banded_spd(n, bw, seed=n + bw)
        b = C.bind(np.random.default_rng(n).standard_normal(n)
                   .astype(np.float32))
        dia = sparse.dia_from_dense(a)
        chip = solvers.cg_solve(dia, b, stop=1e-12, max_iters=2 * n)
        with use_level(ExecLevel.O3, mesh8):
            mesh = solvers.cg_solve(dia, b, stop=1e-12, max_iters=2 * n)
        np.testing.assert_allclose(mesh.x.read(), chip.x.read(),
                                   rtol=1e-5, atol=1e-5)
        # same convergence trajectory, not just the same fixed point
        assert int(mesh.iterations) == int(chip.iterations)
        # and the solve actually solved the system
        rel = (np.linalg.norm(a.astype(np.float32) @ mesh.x.read() - b.read())
               / np.linalg.norm(b.read()))
        assert rel < 1e-3

    def test_mesh_cg_via_csr_and_ell(self, mesh8):
        """The distributed solve composes with every solver_spmv layout."""
        n = 256
        a = sparse.banded_spd(n, 7, seed=9)
        b = C.bind(np.random.default_rng(9).standard_normal(n)
                   .astype(np.float32))
        csr = sparse.csr_from_dense(a)
        chip = solvers.cg_solve(csr, b, stop=1e-12, max_iters=2 * n)
        for m in (csr, sparse.ell_from_csr(csr)):
            with use_level(ExecLevel.O3, mesh8):
                got = solvers.cg_solve(m, b, stop=1e-12, max_iters=2 * n)
            np.testing.assert_allclose(got.x.read(), chip.x.read(),
                                       rtol=1e-5, atol=1e-5)

    def test_mesh_cg_rejects_mismatched_pin(self, mesh8):
        """A pinned mesh variant that names a different layout's
        partitioning is an error, not a silent substitution."""
        a, _ = _banded(128, 3)
        b = C.bind(np.random.default_rng(0).standard_normal(128)
                   .astype(np.float32))
        dia = sparse.dia_from_dense(a)
        with use_level(ExecLevel.O3, mesh8):
            with pytest.raises(ValueError, match="row-partitions"):
                solvers.cg_solve(dia, b, backend="mesh_ell")

    def test_fft_twiddle_cache_hit_across_calls(self, mesh8, rng):
        """The corner-turn twiddle table is plan-cached, not re-exp'd per
        call (ROADMAP item): two solves share one (n, subgrid, dtype)
        entry."""
        from repro.distributed import numerics as dnum

        z = jnp.asarray(rng.standard_normal(512)
                        + 1j * rng.standard_normal(512), jnp.complex64)
        dnum._fft_twiddles.cache_clear()
        with use_level(ExecLevel.O3, mesh8):
            ops.fft(z)
            ops.fft(z)
        info = dnum._fft_twiddles.cache_info()
        assert info.currsize == 1 and info.hits >= 1

    def test_mesh_cg_backend_pin_still_runs_chip(self, mesh8):
        n = 128
        a = sparse.banded_spd(n, 3, seed=2)
        b = C.bind(np.random.default_rng(2).standard_normal(n)
                   .astype(np.float32))
        dia = sparse.dia_from_dense(a)
        with use_level(ExecLevel.O3, mesh8):
            pinned = solvers.cg_solve(dia, b, backend="dia", max_iters=2 * n)
            auto = solvers.cg_solve(dia, b, max_iters=2 * n)
        np.testing.assert_allclose(pinned.x.read(), auto.x.read(),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# hierarchy: the O4 (2,2,2) mesh and the collectives plane (DESIGN.md §8)
# ---------------------------------------------------------------------------

class TestHierarchicalO4:
    def test_reduce_plan_schedules(self, mesh8, mesh222):
        """O4 emits reduce-scatter intra-pod + all-reduce inter-pod; O3
        degenerates to the flat single-axis schedule (PR 2's behaviour)."""
        plan4 = collectives.reduce_plan(mesh222)
        assert plan4.hierarchical
        assert plan4.batch_axes == ("pod", "data") and plan4.width == 4
        assert plan4.schedule("reduce_scatter") == (
            ("reduce_scatter", "data"), ("all_reduce", "pod"))
        plan3 = collectives.reduce_plan(mesh8)
        assert not plan3.hierarchical
        assert plan3.schedule("reduce_scatter") == (("reduce_scatter", "data"),)

    def test_select_context_carries_topology(self, mesh222):
        with use_level(ExecLevel.O4, mesh222):
            ctx = registry.select_context()
        assert ctx.mesh_rank == 3
        assert ctx.topology.roles == ("pod", "data", "model")
        assert ctx.topology.describe() == "pod2xdata2xmodel2"
        assert registry.select_context().mesh_rank == 0     # restored

    def test_axis_roles_declaration_drives_the_plan(self):
        """Exotic axis names become a hierarchy via the scoped role map."""
        from repro.core import axis_roles

        mesh = jax.make_mesh((2, 4), ("replica", "shard"),
                             (jax.sharding.AxisType.Auto,) * 2)
        with axis_roles(replica="pod", shard="data"):
            plan = collectives.reduce_plan(mesh)
        assert plan.hierarchical and plan.pod_axes == ("replica",)
        # without the declaration, unknown names default to batch-like data
        flat = collectives.reduce_plan(mesh)
        assert not flat.hierarchical and flat.width == 8

    def test_o4_selects_2d_matmul_and_degrades(self, mesh8, mesh222):
        """mod2am: 2-D (data, model) variant on O4, 1-D on O3, chip with no
        mesh — same call, no program-text change (acceptance criterion)."""
        a = jnp.ones((64, 64), jnp.float32)
        assert registry.select("matmul", a, a).scope == "chip"
        with use_level(ExecLevel.O4, mesh222):
            assert registry.select("matmul", a, a).name == "mesh_psum_2d"
            # N not divisible by the model tile -> 1-D K-partition form
            b_odd = jnp.ones((64, 95), jnp.float32)
            assert registry.select("matmul", a, b_odd).name == "mesh_psum"
            # K not divisible by pod*data -> chip
            odd = jnp.ones((63, 63), jnp.float32)
            assert registry.select("matmul", odd, odd).scope == "chip"
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("matmul", a, a).name == "mesh_psum"

    def test_o4_matmul_2d_matches_chip(self, mesh222, rng):
        a = jnp.asarray(rng.standard_normal((64, 128)))
        b = jnp.asarray(rng.standard_normal((128, 96)))
        want = np.asarray(ops.matmul(a, b))
        with use_level(ExecLevel.O4, mesh222):
            assert registry.select("matmul", a, b).name == "mesh_psum_2d"
            got = np.asarray(ops.matmul(a, b))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_o4_spmv_all_layouts_match_dense(self, mesh222):
        a, x = _banded()
        csr = sparse.csr_from_dense(a)
        mats = {"mesh_csr": csr, "mesh_ell": sparse.ell_from_csr(csr),
                "mesh_dia": sparse.dia_from_dense(a)}
        want = a.astype(np.float32) @ x.read()
        for name, m in mats.items():
            with use_level(ExecLevel.O4, mesh222):
                assert registry.select("solver_spmv", m, x).name == name
                got = registry.dispatch("solver_spmv", m, x).read()
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_o4_fft_matches_reference(self, mesh222, rng):
        z = jnp.asarray(rng.standard_normal(512)
                        + 1j * rng.standard_normal(512), jnp.complex64)
        want = np.fft.fft(np.asarray(z))
        with use_level(ExecLevel.O4, mesh222):
            assert registry.select("fft", z).name == "mesh_transpose"
            got = np.asarray(ops.fft(z))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)

    @pytest.mark.parametrize("n,bw", [(256, 31)])
    def test_o4_cg_matches_chip_on_table2(self, mesh222, n, bw):
        """Pod-aware CG == single-chip CG to 1e-5 on the paper Table-2
        case, same convergence trajectory (acceptance criterion)."""
        a = sparse.banded_spd(n, bw, seed=n + bw)
        b = C.bind(np.random.default_rng(n).standard_normal(n)
                   .astype(np.float32))
        dia = sparse.dia_from_dense(a)
        chip = solvers.cg_solve(dia, b, stop=1e-12, max_iters=2 * n)
        with use_level(ExecLevel.O4, mesh222):
            hier = solvers.cg_solve(dia, b, stop=1e-12, max_iters=2 * n)
        np.testing.assert_allclose(hier.x.read(), chip.x.read(),
                                   rtol=1e-5, atol=1e-5)
        # same trajectory up to reduction-order rounding: the hierarchical
        # psums sum in a different order than the chip dot, so the stop test
        # may cross the threshold one iteration apart
        assert abs(int(hier.iterations) - int(chip.iterations)) <= 1
        rel = (np.linalg.norm(a.astype(np.float32) @ hier.x.read() - b.read())
               / np.linalg.norm(b.read()))
        assert rel < 1e-3

    def test_o4_indivisible_rows_degrade(self, mesh222):
        """250 rows % 4 (pod*data) != 0 -> chip formulation."""
        a = sparse.banded_spd(250, 3, seed=1)
        x = C.bind(np.random.default_rng(1).standard_normal(250)
                   .astype(np.float32))
        ell = sparse.ell_from_csr(sparse.csr_from_dense(a))
        with use_level(ExecLevel.O4, mesh222):
            assert registry.select("solver_spmv", ell, x).name == "ell"


# ---------------------------------------------------------------------------
# the DIA halo exchange: HPCG's 27-point operator row-sharded over 4 devices
# ---------------------------------------------------------------------------

def _hpcg27(grid):
    """HPCG's 27-point operator on an nx·ny·nz grid (x fastest): 26 on the
    diagonal, -1 for every in-grid neighbour.  Returns (DIA, dense f64)."""
    nx, ny, nz = grid
    n = nx * ny * nz
    steps = [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
             for dx in (-1, 0, 1)]
    i = np.arange(n)
    x, y, z = i % nx, (i // nx) % ny, i // (nx * ny)
    dense = np.zeros((n, n))
    by_off = {}
    for dx, dy, dz in steps:
        off = dx + nx * (dy + ny * dz)
        ok = ((0 <= x + dx) & (x + dx < nx) & (0 <= y + dy) & (y + dy < ny)
              & (0 <= z + dz) & (z + dz < nz))
        by_off[off] = np.where(off == 0, 26.0, np.where(ok, -1.0, 0.0))
        dense[i[ok], i[ok] + off] = 26.0 if off == 0 else -1.0
    offsets = tuple(sorted(by_off))
    diags = jnp.asarray(np.stack([by_off[o] for o in offsets]), jnp.float32)
    return sparse.DIA(diags=diags, offsets=offsets, shape=(n, n)), dense


#: 4 shards of 256 rows; max|offset| = 8*8 + 8 + 1 = 73
HPCG_GRID = (8, 8, 16)


def _textbook_cg(dense, b, iters):
    """``iters`` iterations of textbook CG from x0 = 0 in f32, every
    product at HIGHEST precision on the dense operator."""
    a = jnp.asarray(dense, jnp.float32)
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    x, r, p = jnp.zeros(len(b)), jnp.asarray(b), jnp.asarray(b)
    rr = dot(r, r)
    for _ in range(iters):
        ap = dot(a, p)
        alpha = rr / dot(p, ap)
        x, r = x + alpha * p, r - alpha * ap
        rr, rr_old = dot(r, r), rr
        p = r + (rr / rr_old) * p
    return x


@pytest.fixture
def mesh4():
    return jax.make_mesh((4, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:4])


def _halo_of_each_shard(plan, x, rows):
    entry = plan.spec_entry()

    return jax.jit(jax.shard_map(lambda xl: plan.halo(xl, rows), mesh=plan.mesh, in_specs=P(entry),
                                 out_specs=(P(entry), P(entry)),
                                 check_vma=False))(x)


class TestHaloExchange:
    @pytest.mark.parametrize("mesh_name", ["mesh4", "mesh222"])
    def test_reduce_plan_halo(self, mesh_name, request):
        """Shard k gets the last rows of shard k - 1 and the first rows of
        shard k + 1 in the pod-major order rows shard by; the end shards
        get zeros.  On (pod, data, model) every model replica alike."""
        plan = collectives.reduce_plan(request.getfixturevalue(mesh_name))
        w, per, rows = plan.width, 24, 5
        x = jnp.arange(1, w * per + 1, dtype=jnp.float32)
        lo, hi = (np.asarray(v).reshape(w, rows)
                  for v in _halo_of_each_shard(plan, x, rows))
        xs = np.asarray(x).reshape(w, per)
        for k in range(w):
            want_lo = xs[k - 1, -rows:] if k > 0 else np.zeros(rows)
            want_hi = xs[k + 1, :rows] if k < w - 1 else np.zeros(rows)
            np.testing.assert_array_equal(lo[k], want_lo)
            np.testing.assert_array_equal(hi[k], want_hi)

    def test_mesh_spmv_dia_matches_reference(self, mesh4):
        dia, dense = _hpcg27(HPCG_GRID)
        x = np.random.default_rng(5).standard_normal(dia.shape[0]) \
            .astype(np.float32)
        want = np.asarray(jnp.dot(jnp.asarray(dense, jnp.float32),
                                  jnp.asarray(x),
                                  precision=jax.lax.Precision.HIGHEST))
        with use_level(ExecLevel.O3, mesh4):
            assert registry.select("solver_spmv", dia,
                                   C.bind(x)).name == "mesh_dia"
            got = registry.dispatch("solver_spmv", dia, C.bind(x)).read()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)

    def test_cg_mesh_dia_matches_reference(self, mesh4):
        """50 iterations of the mesh CG against textbook CG in f32 at
        HIGHEST precision on the dense operator."""
        dia, dense = _hpcg27(HPCG_GRID)
        n = dia.shape[0]
        b = (1.0 + np.random.default_rng(6).standard_normal(n)) \
            .astype(np.float32)
        x = _textbook_cg(dense, b, 50)
        with use_level(ExecLevel.O3, mesh4):
            got = solvers.cg_solve(dia, C.bind(b), stop=0.0, max_iters=50)
        assert int(got.iterations) == 50
        np.testing.assert_allclose(got.x.read(), np.asarray(x),
                                   rtol=1e-4, atol=1e-5)

    def test_cg_mesh_dia_takes_p_ap_from_the_kernel(self, mesh4):
        """The mesh CG on DIA with each shard's p.Ap from its own kernel
        launch, interpreted: 256-row shards end in a ragged 1024-lane tile
        whose lanes past the shard hold the 73-row high halo.  It matches
        the chip CG, itself fused, and textbook CG."""
        from repro.obs import METRICS

        dia, dense = _hpcg27(HPCG_GRID)
        n = dia.shape[0]
        b = (1.0 + np.random.default_rng(7).standard_normal(n)) \
            .astype(np.float32)
        fused = METRICS.counter("kernels.spmv_dia.dot_fused")
        jax.clear_caches()              # the counter is set as launches trace
        before = fused.value
        with registry.use_backend("interpret"):
            chip = solvers.cg_solve(dia, C.bind(b), stop=0.0, max_iters=40)
            assert fused.value == before + 1
            with use_level(ExecLevel.O3, mesh4):
                got = solvers.cg_solve(dia, C.bind(b), stop=0.0,
                                       max_iters=40)
        assert fused.value == before + 2
        assert int(got.iterations) == int(chip.iterations) == 40
        np.testing.assert_allclose(got.x.read(), chip.x.read(),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got.x.read(),
                                   np.asarray(_textbook_cg(dense, b, 40)),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("layout", ["csr", "ell"])
    def test_cg_mesh_ell_and_csr_take_p_ap_apart(self, mesh4, layout):
        """ELL and CSR keep their own p.Ap pass: no DIA launch is built
        with the dot, and the mesh CG matches textbook CG as before."""
        from repro.obs import METRICS

        dia, dense = _hpcg27(HPCG_GRID)
        csr = sparse.csr_from_dense(dense.astype(np.float32))
        a = csr if layout == "csr" else sparse.ell_from_csr(csr)
        b = (1.0 + np.random.default_rng(8).standard_normal(dia.shape[0])) \
            .astype(np.float32)
        fused = METRICS.counter("kernels.spmv_dia.dot_fused")
        before = fused.value
        with registry.use_backend("interpret"), \
                use_level(ExecLevel.O3, mesh4):
            got = solvers.cg_solve(a, C.bind(b), stop=0.0, max_iters=30)
        assert fused.value == before
        assert int(got.iterations) == 30
        np.testing.assert_allclose(got.x.read(),
                                   np.asarray(_textbook_cg(dense, b, 30)),
                                   rtol=1e-4, atol=1e-5)

    def test_narrow_shard_degrades_to_chip(self, mesh4):
        """64 rows a shard < max|offset| 73: the halo would need rows of
        more than one neighbour, so the chip formulation runs."""
        dia, _ = _hpcg27((8, 8, 4))
        x = C.bind(np.ones(dia.shape[0], np.float32))
        with use_level(ExecLevel.O3, mesh4):
            assert registry.select("solver_spmv", dia, x).name == "dia"

    def test_cg_loop_exchanges_a_halo(self, mesh4):
        """The mesh CG loop's HLO: ``collective-permute``s for the halo, no
        all-gather of p and no pad; the exchange gauge reads the bytes one
        SpMV receives, 2 * max|offset| * 4.  On the XLA plane, whose SpMV
        the CPU compiles as it is (interpret mode pads Pallas blocks)."""
        from repro.obs import METRICS

        dia, _ = _hpcg27(HPCG_GRID)
        gauge = METRICS.gauge("distributed.mesh_dia.exchange_bytes_per_iter")
        gauge.set(0)

        def solve(d, b):
            a = sparse.DIA(diags=d, offsets=dia.offsets, shape=dia.shape)
            return C.unwrap(solvers.cg_solve(a, b, stop=0.0, max_iters=5).x)

        with use_level(ExecLevel.O3, mesh4), registry.use_backend("xla"):
            hlo = jax.jit(solve).lower(
                dia.diags, jnp.ones(dia.shape[0], jnp.float32)
            ).compile().as_text()
        body = _called_from(hlo, re.search(r"while\(.*\bbody=%([\w.-]+)",
                                           hlo).group(1))
        assert "collective-permute" in body
        assert "all-gather" not in body
        assert "pad(" not in body
        assert gauge.value == 2 * 73 * 4

    @pytest.mark.parametrize("level,mesh_name,variant", [
        (ExecLevel.O2, None, "dia"), (ExecLevel.O3, "mesh4", "mesh_dia")],
        ids=["chip", "mesh4"])
    def test_both_scopes_run_the_solvers_loop(self, level, mesh_name,
                                              variant, request, monkeypatch):
        """One CG loop for one chip and the mesh: with the solver's
        ``arbb_while`` made to return its initial state, ``cg_solve``
        returns x = 0 after 0 iterations in either scope."""
        dia, _ = _hpcg27(HPCG_GRID)
        b = C.bind(np.ones(dia.shape[0], np.float32))
        mesh = request.getfixturevalue(mesh_name) if mesh_name else None
        jax.clear_caches()              # no loop traced before the patch
        try:
            with monkeypatch.context() as m, use_level(level, mesh):
                m.setattr(solvers, "arbb_while",
                          lambda cond, body, init: init)
                assert registry.select("solver_spmv", dia,
                                       b).name == variant
                res = solvers.cg_solve(dia, b, stop=0.0, max_iters=5)
                x, k = res.x.read(), int(res.iterations)
        finally:
            jax.clear_caches()          # none traced with it after
        assert k == 0
        np.testing.assert_array_equal(x, np.zeros(dia.shape[0]))


def _called_from(hlo, name):
    """The text of HLO computation ``name`` and of every computation it
    calls, transitively."""
    comps = dict(re.findall(r"^(?:ENTRY )?%([\w.-]+) .*?\{\n(.*?)^\}",
                            hlo, re.M | re.S))
    seen, todo = set(), [name]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.-]+)",
                               comps[c])
    return "\n".join(comps[c] for c in seen)
