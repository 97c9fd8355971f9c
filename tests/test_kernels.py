"""Per-Pallas-kernel validation: shape/dtype sweeps in interpret mode
against the pure-jnp oracles in repro.kernels.ref (the required kernel
correctness contract — kernel bodies execute in Python on CPU here; the
same pallas_call lowers for TPU in production)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _arr(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


MM_SHAPES = [(8, 8, 8), (128, 128, 128), (96, 80, 112), (1, 7, 3),
             (130, 257, 129), (256, 64, 192)]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_sweep(m, k, n, dtype):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    a, b = _arr(rng, (m, k), dtype), _arr(rng, (k, n), dtype)
    with ops.backend("interpret"):
        out = ops.matmul(a, b)
    want = ref.matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("block", [(32, 32, 32), (64, 128, 32)])
def test_matmul_block_shape_invariance(block):
    rng = np.random.default_rng(0)
    a, b = _arr(rng, (100, 70), jnp.float32), _arr(rng, (70, 90), jnp.float32)
    bm, bn, bk = block
    with ops.backend("interpret"):
        out = ops.matmul(a, b, block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(a, b)),
                               rtol=2e-5, atol=1e-4)


ELL_CASES = [(16, 4), (40, 9), (64, 1), (100, 17), (8, 8)]


@pytest.mark.parametrize("nrows,width", ELL_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_spmv_ell_kernel_sweep(nrows, width, dtype):
    rng = np.random.default_rng(nrows * 31 + width)
    vals = jnp.asarray(rng.standard_normal((nrows, width)), dtype)
    cols = jnp.asarray(rng.integers(0, nrows, (nrows, width)), jnp.int32)
    x = jnp.asarray(rng.standard_normal(nrows), dtype)
    with ops.backend("interpret"):
        out = ops.spmv_ell(vals, cols, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.spmv_ell_ref(vals, cols, x)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("x_vmem_bytes", [1 << 24, 0],
                         ids=["x_in_vmem", "x_in_hbm_windows"])
def test_spmv_ell_kernel_multi_block_banded(x_vmem_bytes):
    """Several row blocks, a band wider than one sub-panel plus far
    columns, and both x placements (whole in VMEM / DMA windows)."""
    from repro.kernels import spmv as spmv_k

    n, width = 5000, 6
    rng = np.random.default_rng(7)
    rows = np.arange(n)[:, None]
    cols = np.clip(rows + np.array([-2100, -130, -1, 0, 1, 2100]), 0, n - 1)
    vals = rng.standard_normal((n, width)).astype(np.float32)
    vals[::7, 3] = 0.0                                  # padding-like holes
    x = rng.standard_normal(n).astype(np.float32)
    out = spmv_k.spmv_ell(jnp.asarray(vals), jnp.asarray(cols, jnp.int32),
                          jnp.asarray(x), block_rows=2048,
                          x_vmem_bytes=x_vmem_bytes, interpret=True)
    want = (vals * x[cols]).sum(axis=1)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-4)


DIA_CASES = [
    (32, (0,)), (32, (-1, 0, 1)), (64, (-3, -1, 0, 1, 3)), (128, (-31, 0, 31)),
    # several tiles, max|offset| = tile: the halo is a whole neighbour tile
    (3 * 8192, (-8192, -1, 0, 1, 8192)),
    # max|offset| < tile, ragged last tile: its lanes past n are masked
    (5 * 8192 + 640, (-300, 0, 300)),
    # the offset raises the tile, and the last tile is ragged
    (40_000, (-10_000, -100, 0, 100, 10_000)),
    # one-sided offsets: the low halo is never read
    (20_000, (0, 1, 5000)),
]


@pytest.mark.parametrize("n,offsets", DIA_CASES)
def test_spmv_dia_kernel_sweep(n, offsets):
    rng = np.random.default_rng(n + len(offsets))
    diags = jnp.asarray(rng.standard_normal((len(offsets), n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    with ops.backend("interpret"):
        out = ops.spmv_dia(diags, offsets, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.spmv_dia_ref(diags, offsets, x)),
                               rtol=1e-4, atol=1e-4)


def _stencil27_offsets(nx, ny):
    return tuple(sorted({dx + nx * (dy + ny * dz) for dz in (-1, 0, 1)
                         for dy in (-1, 0, 1) for dx in (-1, 0, 1)}))


DIA_HALO_CASES = [
    # several tiles, max|offset| = tile, n a multiple of 1024: x and y as
    # (1, n) views
    (3 * 8192, (-8192, -1, 0, 1, 8192)),
    # ragged last tile, x and y flat: the high halo starts inside it
    (5 * 8192 + 640, (-300, 0, 300)),
    (40_000, (-10_000, -100, 0, 100, 10_000)),
    # one-sided offsets: the low halo is never read
    (20_000, (0, 1, 5000)),
    # one tile, shorter than the tile
    (1000, (-3, 0, 3)),
    # the 27-point operator of a 12 x 12 x 10 block: max|offset| 157
    (12 * 12 * 10, _stencil27_offsets(12, 12)),
]


@pytest.mark.parametrize("n,offsets", DIA_HALO_CASES)
def test_spmv_dia_kernel_halo(n, offsets):
    """A row shard with its neighbours' rows: the kernel on (x, halo)
    equals the whole-vector SpMV's rows of the shard, and ``halo=None``
    equals zero halos."""
    m = max(abs(o) for o in offsets)
    rng = np.random.default_rng(n + m)
    diags = rng.standard_normal((len(offsets), n)).astype(np.float32)
    whole = rng.standard_normal(n + 2 * m).astype(np.float32)
    want = sum(diags[d] * whole[m + off:m + off + n]
               for d, off in enumerate(offsets))
    x = jnp.asarray(whole[m:m + n])
    halo = (jnp.asarray(whole[:m]), jnp.asarray(whole[m + n:]))
    zeros = (jnp.zeros(m, jnp.float32), jnp.zeros(m, jnp.float32))
    d = jnp.asarray(diags)
    with ops.backend("interpret"):
        got = ops.spmv_dia(d, offsets, x, halo=halo)
        none = ops.spmv_dia(d, offsets, x)
        zero = ops.spmv_dia(d, offsets, x, halo=zeros)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(none), np.asarray(zero))
    np.testing.assert_allclose(
        np.asarray(ref.spmv_dia_ref(d, offsets, x, halo)), want,
        rtol=1e-4, atol=1e-4)


def test_spmv_dia_fetches_each_x_tile_once():
    """The launch plan's x traffic, set at trace time: each of the T row
    tiles of x is fetched once (a window of three reads would be 12·T·tile).
    The shape is this test's own, so the call traces afresh."""
    from repro.obs import METRICS

    n, offsets, tile = 3 * 8192 + 256, (-2, 0, 2), 8192
    gauge = METRICS.gauge("kernels.spmv_dia.x_bytes_per_launch")
    gauge.set(0)
    diags = jnp.ones((len(offsets), n), jnp.float32)
    with ops.backend("interpret"):
        ops.spmv_dia(diags, offsets, jnp.ones(n, jnp.float32))
    assert gauge.value == 4 * 4 * tile


def _dia_dot(diags, offsets, x, halo=None):
    """The kernel with and without ``dot=True``, interpreted: (y without,
    y with, the dot)."""
    from repro.kernels import spmv as spmv_k

    y = spmv_k.spmv_dia(diags, offsets, x, halo=halo, interpret=True)
    y_dot, xy = spmv_k.spmv_dia(diags, offsets, x, halo=halo, dot=True,
                                interpret=True)
    return np.asarray(y), np.asarray(y_dot), float(xy)


def _assert_dot_of(x, y, xy):
    want = np.vdot(np.asarray(x, np.float64), np.asarray(y, np.float64))
    assert abs(xy - want) <= 1e-5 * abs(want), (xy, want)


@pytest.mark.parametrize("n,offsets", DIA_CASES)
def test_spmv_dia_kernel_dot(n, offsets):
    """``dot=True``: y bit for bit as without it, and x . y over the rows."""
    rng = np.random.default_rng(n + len(offsets))
    diags = jnp.asarray(rng.standard_normal((len(offsets), n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    y, y_dot, xy = _dia_dot(diags, offsets, x)
    np.testing.assert_array_equal(y_dot, y)
    _assert_dot_of(x, y, xy)


# every halo case but the first ends in a ragged tile
@pytest.mark.parametrize("n,offsets", DIA_HALO_CASES[1:])
def test_spmv_dia_kernel_dot_ragged_tile_with_halo(n, offsets):
    """A ragged last tile whose lanes past n hold the high halo (and the
    diagonals' unspecified lanes): they add nothing to x . y, which is over
    the shard's own rows."""
    m = max(abs(o) for o in offsets)
    rng = np.random.default_rng(n + 2 * m)
    diags = jnp.asarray(rng.standard_normal((len(offsets), n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    halo = tuple(jnp.asarray(100.0 + 10.0 * rng.standard_normal(m),
                             jnp.float32) for _ in range(2))
    y, y_dot, xy = _dia_dot(diags, offsets, x, halo)
    np.testing.assert_array_equal(y_dot, y)
    _assert_dot_of(x, y, xy)


@pytest.mark.parametrize("plane", ["interpret", "xla"])
def test_spmv_dia_dots_on_every_plane(plane):
    """Inside ``spmv_dia_dots`` the op returns y as before and hands x . y
    to the list; only a launch built with ``dot=True`` counts as fused."""
    from repro.obs import METRICS

    n, offsets = 3000, (-7, 0, 7)
    rng = np.random.default_rng(3)
    diags = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    fused = METRICS.counter("kernels.spmv_dia.dot_fused")
    jax.clear_caches()                  # the counter is set as launches trace
    with ops.backend(plane):
        before = fused.value
        y = ops.spmv_dia(diags, offsets, x)
        assert fused.value == before
        with ops.spmv_dia_dots() as dots:
            y_dot = ops.spmv_dia(diags, offsets, x)
    np.testing.assert_array_equal(np.asarray(y_dot), np.asarray(y))
    (xy,) = dots
    _assert_dot_of(x, y, float(xy))
    assert fused.value == before + (plane == "interpret")


def test_spmv_dia_kernel_counts_dot_launches():
    """``kernels.spmv_dia.dot_fused`` counts the launches built with
    ``dot=True``, at trace time, and no other."""
    from repro.obs import METRICS

    fused = METRICS.counter("kernels.spmv_dia.dot_fused")
    before = fused.value
    diags = jnp.ones((3, 512), jnp.float32)
    _dia_dot(diags, (-1, 0, 1), jnp.ones(512, jnp.float32))
    assert fused.value == before + 1


@pytest.mark.parametrize("logn", [3, 6, 8, 10, 12])
def test_fft_kernel_sweep(logn):
    n = 1 << logn
    rng = np.random.default_rng(logn)
    z = jnp.asarray(rng.standard_normal(n) + 1j * rng.standard_normal(n),
                    jnp.complex64)
    with ops.backend("interpret"):
        out = ops.fft(z)
    np.testing.assert_allclose(np.asarray(out), np.fft.fft(np.asarray(z)),
                               rtol=1e-2, atol=1e-3 * n)


FA_SHAPES = [(1, 1, 128, 16), (2, 4, 128, 32), (1, 2, 256, 64),
             (2, 8, 384, 16)]


@pytest.mark.parametrize("b,h,l,d", FA_SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_kernel_sweep(b, h, l, d, causal):
    rng = np.random.default_rng(b + h + l + d)
    q = _arr(rng, (b, h, l, d), jnp.float32)
    k = _arr(rng, (b, h, l, d), jnp.float32)
    v = _arr(rng, (b, h, l, d), jnp.float32)
    with ops.backend("interpret"):
        out = ops.flash_attention(q, k, v, causal=causal)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_gqa_via_xla_path():
    """GQA head-broadcast correctness on the dispatch wrapper (xla ref)."""
    rng = np.random.default_rng(5)
    q = _arr(rng, (2, 8, 64, 16), jnp.float32)
    k = _arr(rng, (2, 2, 64, 16), jnp.float32)
    v = _arr(rng, (2, 2, 64, 16), jnp.float32)
    with ops.backend("xla"):
        out = ops.flash_attention(q, k, v, causal=True)
    # manual GQA oracle
    kk = jnp.repeat(k, 4, axis=1)
    vv = jnp.repeat(v, 4, axis=1)
    want = ref.attention_ref(q, kk, vv, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("lq,lk", [(4096, 4096), (2048, 4096)])
@pytest.mark.parametrize("causal", [True, False])
def test_attention_chunked_matches_oracle(lq, lk, causal):
    """The flash-schedule XLA path (§Perf iter 2) vs the materialising
    oracle, fwd and grad."""
    rng = np.random.default_rng(lq + lk)
    q = _arr(rng, (1, 2, lq, 16), jnp.float32)
    k = _arr(rng, (1, 1, lk, 16), jnp.float32)
    v = _arr(rng, (1, 1, lk, 16), jnp.float32)
    want = ref.attention_ref(q, k, v, causal=causal)
    got = ref.attention_chunked(q, k, v, causal=causal, block_kv=1024)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    g1 = jax.grad(lambda x: ref.attention_ref(x, k, v, causal=causal).sum())(q)
    g2 = jax.grad(lambda x: ref.attention_chunked(
        x, k, v, causal=causal).sum())(q)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                               rtol=1e-3, atol=1e-4)


def test_backend_dispatch_default_is_xla_on_cpu(monkeypatch):
    monkeypatch.delenv("REPRO_KERNELS", raising=False)  # test.sh sets it
    assert ops.current_backend() == "xla"
    with ops.backend("interpret"):
        assert ops.current_backend() == "interpret"
    assert ops.current_backend() == "xla"


def test_xla_and_interpret_paths_agree():
    rng = np.random.default_rng(9)
    a, b = _arr(rng, (64, 48), jnp.float32), _arr(rng, (48, 80), jnp.float32)
    with ops.backend("xla"):
        ox = ops.matmul(a, b)
    with ops.backend("interpret"):
        oi = ops.matmul(a, b)
    np.testing.assert_allclose(np.asarray(ox), np.asarray(oi),
                               rtol=1e-5, atol=1e-5)


class TestKvLenPrefixMask:
    """``flash_attention_state(kv_len=...)`` — the prefix-valid masking the
    paged serve tier decodes through (DESIGN.md §13)."""

    def _qkv(self, b=2, hk=2, l=64, d=16):
        rng = np.random.default_rng(7)
        q = _arr(rng, (b, 4, 1, d), jnp.float32)
        k = _arr(rng, (b, hk, l, d), jnp.float32)
        v = _arr(rng, (b, hk, l, d), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("variant", ["interpret", "xla"])
    def test_kv_len_equals_manual_slice(self, variant):
        """Masked full-buffer attention == attention over the valid slice,
        per batch row, for both the lens kernel and the XLA reference."""
        q, k, v = self._qkv()
        lens = jnp.asarray([13, 64], jnp.int32)
        o, m, l = ops.flash_attention_state(q, k, v, causal=False,
                                            kv_len=lens, variant=variant)
        for b in range(2):
            n = int(lens[b])
            ow, _, _ = ops.flash_attention_state(
                q[b:b + 1], k[b:b + 1, :, :n], v[b:b + 1, :, :n],
                causal=False, variant="xla")
            np.testing.assert_allclose(np.asarray(o[b]), np.asarray(ow[0]),
                                       rtol=1e-5, atol=1e-5)

    def test_lens_kernel_matches_ref(self):
        from repro.kernels import ref as ref_k

        q, k, v = self._qkv()
        lens = jnp.asarray([29, 48], jnp.int32)
        ok, mk, lk = ops.flash_attention_state(q, k, v, causal=False,
                                               kv_len=lens,
                                               variant="interpret")
        ow, mw, lw = ref_k.attention_state_ref(q, k, v, causal=False,
                                               kv_len=lens)
        np.testing.assert_allclose(np.asarray(ok), np.asarray(ow),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(mk), np.asarray(mw),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lw),
                                   rtol=1e-5, atol=1e-5)


class TestMergeStates:
    """Online-softmax state algebra — the decode-side dual of the ring
    rotation's accumulator (DESIGN.md §10 → §13)."""

    def test_split_merge_equals_whole(self):
        from repro.kernels import flash_attention as fa_k

        rng = np.random.default_rng(3)
        q = _arr(rng, (2, 4, 1, 16), jnp.float32)
        k = _arr(rng, (2, 2, 64, 16), jnp.float32)
        v = _arr(rng, (2, 2, 64, 16), jnp.float32)
        whole = ops.flash_attention_state(q, k, v, causal=False,
                                          variant="xla")
        a = ops.flash_attention_state(q, k[:, :, :40], v[:, :, :40],
                                      causal=False, variant="xla")
        b = ops.flash_attention_state(q, k[:, :, 40:], v[:, :, 40:],
                                      causal=False, variant="xla")
        o, m, l = fa_k.merge_states(a, b)
        np.testing.assert_allclose(np.asarray(o), np.asarray(whole[0]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l), np.asarray(whole[2]),
                                   rtol=1e-5, atol=1e-5)

    def test_all_masked_state_is_identity(self):
        """A kv_len=0 shard carries m == NEG_INF and merges as a no-op —
        how empty ring shards cancel in the paged decode merge."""
        from repro.kernels import flash_attention as fa_k

        rng = np.random.default_rng(4)
        q = _arr(rng, (1, 4, 1, 16), jnp.float32)
        k = _arr(rng, (1, 2, 32, 16), jnp.float32)
        v = _arr(rng, (1, 2, 32, 16), jnp.float32)
        full = ops.flash_attention_state(q, k, v, causal=False,
                                         variant="xla")
        empty = ops.flash_attention_state(
            q, k, v, causal=False, kv_len=jnp.zeros((1,), jnp.int32),
            variant="xla")
        o, m, l = fa_k.merge_states(full, empty)
        np.testing.assert_allclose(np.asarray(o), np.asarray(full[0]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(l), np.asarray(full[2]),
                                   rtol=1e-6, atol=1e-6)
