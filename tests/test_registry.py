"""The unified operator registry: selection rules, fallbacks, autotune cache.

The contracts under test are DESIGN.md §6's selection rules — explicit
variant > requested plane > capability/cost — and the blocking layer's
autotune persistence."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blocking, registry
from repro.kernels import ops, ref


def _mats(n=32):
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    return a, b


# ---------------------------------------------------------------------------
# plane resolution / fallback
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="a pallas request only fails off-TPU")
def test_pallas_requested_off_tpu_falls_back_to_xla(monkeypatch):
    """No fallback: an explicit 'pallas' request off-TPU raises in
    resolution, selection and dispatch, so no run can pass on another plane
    while claiming the chip.  With no request, CPU resolves to xla."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    a, b = _mats()
    assert registry.resolve_backend() == "xla"
    assert registry.select("matmul", a, b).plane == "xla"
    with registry.use_backend("pallas"):
        with pytest.raises(RuntimeError, match="needs a TPU"):
            registry.resolve_backend()
        with pytest.raises(RuntimeError, match="needs a TPU"):
            registry.select("matmul", a, b)
        with pytest.raises(RuntimeError, match="needs a TPU"):
            ops.matmul(a, b)


def test_interpret_forced_selects_interpret_variant():
    a, b = _mats()
    with registry.use_backend("interpret"):
        assert registry.select("matmul", a, b).name == "interpret"
        assert registry.select("fft", a[0].astype(jnp.complex64)).name \
            == "interpret"


def test_env_var_requests_plane(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    assert registry.requested_backend() == "interpret"
    assert registry.resolve_backend() == "interpret"
    # the scoped context still beats the env var
    with registry.use_backend("xla"):
        assert registry.resolve_backend() == "xla"


def test_env_typo_fails_loudly(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpert")
    with pytest.raises(ValueError, match="REPRO_KERNELS"):
        registry.resolve_backend()


def test_unknown_plane_rejected():
    with pytest.raises(ValueError, match="unknown backend plane"):
        with registry.use_backend("cuda"):
            pass


def test_accepts_routes_around_shape_mismatch():
    """A variant whose accepts() fails is skipped even when its plane was
    requested (flash kernel with non-divisible lengths -> xla oracle)."""
    rng = np.random.default_rng(0)
    # a mismatch the kernel can't take: GQA head ratio not integral
    q3 = jnp.asarray(rng.standard_normal((1, 3, 64, 8)), jnp.float32)
    k2 = jnp.asarray(rng.standard_normal((1, 2, 64, 8)), jnp.float32)
    with registry.use_backend("interpret"):
        v = registry.select("flash_attention", q3, k2, k2, causal=False)
    assert v.plane == "xla"


# ---------------------------------------------------------------------------
# registration contracts
# ---------------------------------------------------------------------------

def test_duplicate_variant_rejected():
    registry.register("_test_op", "v1", lambda x: x)
    try:
        with pytest.raises(ValueError, match="duplicate variant"):
            registry.register("_test_op", "v1", lambda x: x + 1)
    finally:
        registry.unregister("_test_op")


def test_explicit_variant_and_layout_autoselection():
    from repro.core import bind
    from repro.numerics import sparse
    a = sparse.banded_spd(64, 3, seed=1)
    x = bind(np.random.default_rng(1).standard_normal(64).astype(np.float32))
    dia = sparse.dia_from_dense(a)
    csr = sparse.csr_from_dense(a)
    # auto-selection keys on the matrix layout
    assert registry.select("solver_spmv", dia, x).name == "dia"
    assert registry.select("solver_spmv", csr, x).name == "spmv2"
    # explicit variant is always honoured
    assert registry.select("solver_spmv", csr, x, variant="spmv1").name \
        == "spmv1"
    # explicit-but-unknown is a clear error
    with pytest.raises(ValueError, match="no variant"):
        registry.select("solver_spmv", csr, x, variant="nope")
    y_auto = registry.dispatch("solver_spmv", dia, x).read()
    y_csr = registry.dispatch("solver_spmv", csr, x, variant="spmv2").read()
    np.testing.assert_allclose(y_auto, y_csr, rtol=1e-4, atol=1e-4)


def test_unknown_op_is_lookup_error():
    with pytest.raises(LookupError, match="unknown op"):
        registry.dispatch("no_such_op")


# ---------------------------------------------------------------------------
# autotune cache
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrips_through_json(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")

    a, b = _mats(24)
    with registry.use_backend("interpret"):
        out = ops.matmul(a, b)              # first call measures + persists
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-4)

    assert path.exists()
    data = json.loads(path.read_text())
    keys = [k for k in data if k.startswith("matmul|")]
    assert keys, data
    entry = data[keys[0]]
    assert {"m", "n", "k"} <= set(entry)

    # a fresh cache instance reads the same blocks back
    fresh = blocking.AutotuneCache(str(path))
    blocks = fresh.lookup(keys[0])
    assert blocks == {k: int(v) for k, v in entry.items()
                      if not k.startswith("_")}

    # and the next resolve is a pure cache hit (no re-measurement)
    resolved = blocking.resolve_blocks(
        "matmul", {"m": 24, "k": 24, "n": 24}, "float32",
        defaults={"m": 128, "n": 128, "k": 128},
        measure=lambda bl: (_ for _ in ()).throw(AssertionError("re-measured")))
    assert resolved == blocks


def test_autotune_keys_carry_scope_and_mesh(tmp_path, monkeypatch):
    """Mesh-scoped resolutions write ``op|dims|dtype|mesh|<shape>`` keys, so
    per-shard tuning inside shard_map never aliases chip entries of the same
    local shape (DESIGN.md §8)."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8 forced host devices")
    from repro.core import ExecLevel, use_level

    path = tmp_path / "at.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    defaults = {"m": 128, "n": 128, "k": 128}
    dims = {"m": 8, "k": 8, "n": 8}
    assert blocking.ambient_scope_key() == ("chip", "-")
    blocking.resolve_blocks("matmul", dims, "float32", defaults,
                            candidates=({"m": 64},), measure=lambda bl: 1.0)
    mesh = jax.make_mesh((8, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    with use_level(ExecLevel.O3, mesh):
        assert blocking.ambient_scope_key() == ("mesh", "data8xmodel1")
        blocking.resolve_blocks("matmul", dims, "float32", defaults,
                                candidates=({"m": 64},),
                                measure=lambda bl: 1.0)
    data = json.loads(path.read_text())
    assert "matmul|k=8,m=8,n=8|float32|chip|-" in data
    assert "matmul|k=8,m=8,n=8|float32|mesh|data8xmodel1" in data


def test_autotune_legacy_keys_upgrade_to_chip_scope(tmp_path, caplog):
    """Old three-part keys load as chip scope — a mesh-scoped resolution
    misses (re-tunes) instead of silently reusing chip blocks — and the
    upgrade is logged."""
    import logging

    path = tmp_path / "autotune.json"
    path.write_text(json.dumps(
        {"matmul|k=8,m=8,n=8|float32": {"m": 64, "n": 128, "k": 128}}))
    cache = blocking.AutotuneCache(str(path))
    with caplog.at_level(logging.INFO, logger="repro.core.blocking"):
        hit = cache.lookup("matmul|k=8,m=8,n=8|float32|chip|-")
    assert hit == {"m": 64, "n": 128, "k": 128}
    assert cache.lookup(
        "matmul|k=8,m=8,n=8|float32|mesh|data8xmodel1") is None
    assert "legacy" in caplog.text


def test_autotune_disabled_uses_defaults(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    resolved = blocking.resolve_blocks(
        "matmul", {"m": 8, "k": 8, "n": 8}, "float32",
        defaults={"m": 128, "n": 128, "k": 128},
        candidates=({"m": 64},), measure=lambda bl: 0.0)
    assert resolved == {"m": 128, "n": 128, "k": 128}
    assert not (tmp_path / "at.json").exists()
