"""Block-sparse flash attention (DESIGN.md §12): mask compiler + tile-
skipping kernel + density-gated selection.

Contracts under test:
  * compiler — every compiled :class:`TileLayout` round-trips to the
    reference dense mask exactly (``layout.dense() == dense_mask(spec)``),
    across a seeded sweep of random window / global-token / block-pattern
    specs (plus a hypothesis-driven version where hypothesis is installed);
    tile classes, packing order, band metadata and SparseStats all agree
    with the reference tiles;
  * kernel — the tile-skipping kernel == the dense-masked XLA oracle for
    positional and stored-bias specs, MHA/GQA/MQA head layouts, unequal
    Lq/Lk, dead rows, the all-dead early return, and ``return_state``;
    f32 at oracle tolerance, bf16 within 1e-3;
  * causal parity — the row-extent banded layout reproduces the legacy
    ``pl.when`` full-grid causal kernel bitwise (same panel order);
  * selection — rich masks pick ``blocksparse`` on a pallas-grade plane and
    degrade to the materialising oracle elsewhere; trivially-dense causal
    masks stay with the dense kernels (causal tile density > 1/2 >
    ``BLOCKSPARSE_MAX_DENSITY`` is impossible); ``variant=`` pins; the
    static cost tier sits between PALLAS and the chunked XLA path;
  * ring — per-shard state dispatches ride the banded layout under a mesh
    (interpret plane), and rich masks fall off the ring to the chip
    block-sparse path;
  * model — ``attn_window`` / ``attn_global_tokens`` configs lower to a
    MaskSpec and change the attention output.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ExecLevel, registry, use_level
from repro.core.registry import Cost
from repro.kernels import flash_attention as fa_k
from repro.kernels import ops, ref
from repro.sparse.maskcompiler import (DEAD, FULL, PARTIAL, MaskSpec,
                                       causal_layout, compile_layout,
                                       dense_mask)
from repro.sparse.selector import BLOCKSPARSE_MAX_DENSITY
from repro.sparse.stats import SparseStats


def _qkv(B=2, H=4, HK=2, LQ=64, LK=None, D=16, dtype=jnp.float32,
         vscale=1.0, seed=0):
    LK = LQ if LK is None else LK
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, H, LQ, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, HK, LK, D)), dtype)
    v = jnp.asarray(vscale * rng.standard_normal((B, HK, LK, D)), dtype)
    return q, k, v


def _random_spec(rng, lq, lk, bs):
    """One random MaskSpec drawn from the full surface: causal x window x
    global tokens x arbitrary block patterns (any subset, any combination)."""
    causal = bool(rng.integers(2))
    window = int(rng.integers(1, lk + 16)) if rng.integers(2) else None
    gl = (tuple(sorted(rng.choice(lk, size=int(rng.integers(1, 5)),
                                  replace=False).tolist()))
          if rng.integers(2) else ())
    blocks, block = None, 0
    if rng.integers(2):
        block = bs
        pat = rng.random((-(-lq // bs), -(-lk // bs))) < 0.45
        blocks = tuple(tuple(bool(x) for x in row) for row in pat)
    return MaskSpec(causal=causal, window=window, global_tokens=gl,
                    blocks=blocks, block=block)


#: the named specs the kernel tests sweep — one per masking mechanism
_SPECS = {
    "causal_window": lambda lq, lk: MaskSpec(causal=True, window=max(lq // 4, 1)),
    "bidir_window": lambda lq, lk: MaskSpec(window=max(lq // 3, 1)),
    "causal_globals": lambda lq, lk: MaskSpec(causal=True, window=lq // 4,
                                              global_tokens=(0, 1, lk // 2)),
    "block_pattern": lambda lq, lk: MaskSpec.from_block_mask(
        (np.random.default_rng(7).random((lq // 16, lk // 16)) < 0.4)
        | np.eye(lq // 16, lk // 16, k=(lk - lq) // 16, dtype=bool), 16),
    "causal_blocks": lambda lq, lk: MaskSpec.from_block_mask(
        np.random.default_rng(11).random((lq // 16, lk // 16)) < 0.5,
        16, causal=True),
}


# ---------------------------------------------------------------------------
# the mask compiler
# ---------------------------------------------------------------------------

class TestMaskCompiler:
    def test_round_trip_property_sweep(self):
        """The §12 property: compiled layout -> dense tile mask == reference
        mask, over a seeded sweep of random specs (hypothesis is not in the
        image; the sweep is the same property at fixed seeds)."""
        rng = np.random.default_rng(0)
        for trial in range(60):
            lq, lk = rng.choice([32, 64, 96], size=2)
            lq, lk = int(min(lq, lk)), int(max(lq, lk))
            bs = int(rng.choice([16, 32]))
            spec = _random_spec(rng, lq, lk, bs)
            bq = int(rng.choice([16, 32]))
            bk = int(rng.choice([16, 32]))
            if lq % bq or lk % bk:
                continue
            lay = compile_layout(spec, lq, lk, bq, bk)
            want = dense_mask(spec, lq, lk)
            np.testing.assert_array_equal(
                lay.dense(), want,
                err_msg=f"trial {trial}: {spec} at ({lq},{lk})/({bq},{bk})")
            # tile classes agree with the reference tiles
            tiles = want.reshape(lq // bq, bq, lk // bk, bk)
            classes = lay.tile_classes()
            np.testing.assert_array_equal(classes == FULL,
                                          tiles.all(axis=(1, 3)))
            np.testing.assert_array_equal(classes == DEAD,
                                          ~tiles.any(axis=(1, 3)))

    def test_round_trip_hypothesis(self):
        """The same property driven by hypothesis, where installed."""
        hyp = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hyp.given(st.integers(0, 2 ** 31 - 1))
        @hyp.settings(max_examples=25, deadline=None)
        def prop(seed):
            rng = np.random.default_rng(seed)
            spec = _random_spec(rng, 64, 64, 16)
            lay = compile_layout(spec, 64, 64, 16, 16)
            np.testing.assert_array_equal(lay.dense(), dense_mask(spec, 64, 64))

        prop()

    def test_causal_layout_structure(self):
        lay = causal_layout(128, 128, 32, 32)
        assert lay.band == (True, None, 0)
        rowp = np.asarray(lay.rowp)
        mid = np.asarray(lay.mid)
        cols = np.asarray(lay.cols)
        for i in range(4):
            # row i: i full interior tiles then the diagonal partial tile,
            # K-tile indices ascending (the dense kernel's panel order)
            np.testing.assert_array_equal(cols[rowp[i]:rowp[i + 1]],
                                          np.arange(i + 1))
            assert mid[i] == rowp[i] + i
        assert lay.ntiles == 10 and lay.nfull == 6
        # causal tile density is always > 1/2 — trivially-dense masks can
        # never pass the BLOCKSPARSE_MAX_DENSITY gate
        assert lay.density == pytest.approx(10 / 16)
        assert lay.density > BLOCKSPARSE_MAX_DENSITY

    def test_offset_aligns_tails(self):
        m = dense_mask(MaskSpec(causal=True), 32, 96)
        np.testing.assert_array_equal(
            m, np.tril(np.ones((32, 96), bool), k=96 - 32))

    def test_stats_and_density(self):
        pat = np.zeros((4, 4), bool)
        pat[0, 0] = pat[2, 1] = pat[3, 3] = True
        spec = MaskSpec.from_block_mask(pat, 16)
        lay = compile_layout(spec, 64, 64, 16, 16)
        assert isinstance(lay.stats, SparseStats)
        assert lay.density == pytest.approx(3 / 16)
        assert lay.ntiles == 3 and lay.nfull == 3
        # the stats measure the *tile* occupancy matrix
        assert lay.stats.nnz == 3

    def test_cost_dims_fingerprint(self):
        a = MaskSpec(causal=True, window=64)
        b = MaskSpec(causal=True, window=128)
        assert a.cost_dims() != b.cost_dims()
        from repro.core import costmodel
        q, k, v = _qkv(LQ=32)
        sig_a = costmodel.signature((q, k, v), {"mask": a})
        sig_b = costmodel.signature((q, k, v), {"mask": b})
        assert sig_a["mask.window"] == 64
        assert sig_a != sig_b

    def test_validation_errors(self):
        with pytest.raises(ValueError):
            MaskSpec(causal=True, window=0)
        with pytest.raises(ValueError):
            MaskSpec(blocks=((True,),))              # pattern without block
        with pytest.raises(ValueError):
            MaskSpec(block=16)                       # block without pattern
        with pytest.raises(ValueError):              # pattern doesn't cover
            dense_mask(MaskSpec.from_block_mask(np.ones((2, 2), bool), 16),
                       64, 64)
        with pytest.raises(ValueError):              # shape doesn't tile
            compile_layout(MaskSpec(causal=True), 60, 64, 16, 16)


# ---------------------------------------------------------------------------
# the tile-skipping kernel vs the dense-masked oracle
# ---------------------------------------------------------------------------

def _oracle(q, k, v, spec):
    m = jnp.asarray(dense_mask(spec, q.shape[2], k.shape[2]))
    return ref.attention_masked_ref(q, k, v, m)


class TestBlocksparseKernel:
    @pytest.mark.parametrize("name", sorted(_SPECS))
    @pytest.mark.parametrize("heads", [(4, 4), (4, 2), (4, 1)])
    def test_matches_masked_oracle_f32(self, name, heads):
        H, HK = heads
        q, k, v = _qkv(H=H, HK=HK, LQ=64)
        spec = _SPECS[name](64, 64)
        lay = compile_layout(spec, 64, 64, 16, 16)
        got = fa_k.flash_attention_tiles(q, k, v, lay, interpret=True)
        want = _oracle(q, k, v, spec)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("name", ["causal_window", "causal_globals"])
    def test_matches_masked_oracle_bf16(self, name):
        q, k, v = _qkv(H=4, HK=2, LQ=64, dtype=jnp.bfloat16, vscale=0.1)
        spec = _SPECS[name](64, 64)
        lay = compile_layout(spec, 64, 64, 16, 16)
        got = fa_k.flash_attention_tiles(q, k, v, lay, interpret=True)
        want = _oracle(q, k, v, spec)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=1e-3)

    def test_unequal_lengths_offset(self):
        q, k, v = _qkv(LQ=32, LK=96)
        spec = MaskSpec(causal=True, window=40)
        lay = compile_layout(spec, 32, 96, 16, 16)
        got = fa_k.flash_attention_tiles(q, k, v, lay, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_oracle(q, k, v, spec)),
                                   rtol=1e-5, atol=1e-5)

    def test_dead_rows_output_zero(self):
        pat = np.zeros((4, 4), bool)
        pat[0] = True                       # rows 1-3 attend to nothing
        spec = MaskSpec.from_block_mask(pat, 16)
        q, k, v = _qkv(LQ=64)
        lay = compile_layout(spec, 64, 64, 16, 16)
        got = np.asarray(fa_k.flash_attention_tiles(q, k, v, lay,
                                                    interpret=True))
        assert np.all(got[:, :, 16:, :] == 0.0)
        np.testing.assert_allclose(got, np.asarray(_oracle(q, k, v, spec)),
                                   rtol=1e-5, atol=1e-5)

    def test_all_dead_early_return(self):
        spec = MaskSpec.from_block_mask(np.zeros((4, 4), bool), 16)
        q, k, v = _qkv(LQ=64)
        lay = compile_layout(spec, 64, 64, 16, 16)
        assert lay.ntiles == 0
        o, m, l = fa_k.flash_attention_tiles(q, k, v, lay, interpret=True,
                                             return_state=True)
        assert np.all(np.asarray(o) == 0.0)
        assert np.all(np.asarray(m) == fa_k.NEG_INF)
        assert np.all(np.asarray(l) == 0.0)

    def test_causal_row_extents_bitwise_parity(self):
        """The satellite contract: the row-extent banded grid reproduces the
        legacy ``pl.when`` full-grid causal kernel *bitwise* — in-row K-tile
        order is ascending, so f32 accumulation order is identical."""
        q, k, v = _qkv(LQ=128)
        new = fa_k.flash_attention(q, k, v, causal=True, block_q=32,
                                   block_k=32, interpret=True)
        old = fa_k.flash_attention(q, k, v, causal=True, block_q=32,
                                   block_k=32, row_extents=False,
                                   interpret=True)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))

    def test_return_state_matches_state_ref(self):
        q, k, v = _qkv(LQ=64)
        o, m, l = fa_k.flash_attention_tiles(
            q, k, v, causal_layout(64, 64, 16, 16), interpret=True,
            return_state=True)
        ro, rm, rl = ref.attention_state_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(m), np.asarray(rm),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l), np.asarray(rl),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# density-gated selection
# ---------------------------------------------------------------------------

class TestSelection:
    def test_cost_tier_ordering(self):
        assert Cost.BLOCKSPARSE < Cost.PALLAS < Cost.XLA_CHUNKED < Cost.XLA
        assert 0.0 < BLOCKSPARSE_MAX_DENSITY < 1.0
        import repro.sparse as sparse
        assert "BLOCKSPARSE_MAX_DENSITY" in sparse.__all__

    def test_rich_mask_selects_blocksparse_on_interpret_plane(self):
        q, k, v = _qkv(LQ=64)
        spec = MaskSpec(causal=True, window=16)
        with ops.backend("interpret"):
            sel = registry.select("flash_attention", q, k, v, causal=True,
                                  mask=spec)
            assert sel.name == "blocksparse_interpret"
            got = registry.dispatch("flash_attention", q, k, v, causal=True,
                                    mask=spec)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_oracle(q, k, v, spec)),
                                   rtol=1e-5, atol=1e-5)

    def test_rich_mask_degrades_to_oracle_off_pallas(self):
        """With the tile planes pinned away (xla backend), a rich mask
        lands on the materialising masked oracle — numerics never change."""
        q, k, v = _qkv(LQ=64)
        spec = MaskSpec(causal=True, window=16, global_tokens=(0,))
        with ops.backend("xla"):
            sel = registry.select("flash_attention", q, k, v, causal=True,
                                  mask=spec)
            assert sel.plane in ("xla",)
            got = registry.dispatch("flash_attention", q, k, v, causal=True,
                                    mask=spec)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_oracle(q, k, v, spec)),
                                   rtol=1e-5, atol=1e-5)

    def test_trivial_causal_mask_stays_dense(self):
        """Plain causal compiles to density > 1/2, so the density gate keeps
        the dense kernels — with or without the mask spelled as a MaskSpec."""
        q, k, v = _qkv(LQ=64)
        spec = MaskSpec(causal=True)
        for backend in (None, "interpret"):
            ctx = ops.backend(backend) if backend else contextlib.nullcontext()
            with ctx:
                sel = registry.select("flash_attention", q, k, v,
                                      causal=True, mask=spec)
                assert not sel.name.startswith("blocksparse")
                with_mask = registry.dispatch("flash_attention", q, k, v,
                                              causal=True, mask=spec)
                without = registry.dispatch("flash_attention", q, k, v,
                                            causal=True)
            np.testing.assert_array_equal(np.asarray(with_mask),
                                          np.asarray(without))

    def test_variant_pin_overrides_gate(self):
        q, k, v = _qkv(LQ=64)
        spec = MaskSpec(causal=True, window=48)   # densities near the gate
        got = registry.dispatch("flash_attention", q, k, v, causal=True,
                                mask=spec, variant="blocksparse_interpret")
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_oracle(q, k, v, spec)),
                                   rtol=1e-5, atol=1e-5)

    def test_ungrouped_heads_rejected(self):
        q, k, v = _qkv(H=3, HK=2, LQ=64)
        spec = MaskSpec(causal=True, window=16)
        assert not ops._bs_accepts(q, k, v, mask=spec)

    def test_public_wrapper_passes_mask(self):
        q, k, v = _qkv(LQ=64)
        spec = MaskSpec(causal=True, window=16)
        got = ops.flash_attention(q, k, v, mask=spec)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_oracle(q, k, v, spec)),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the ring: banded per-shard layouts, rich masks fall off
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the 8 forced host devices")
class TestRingBanded:
    def test_ring_banded_shards_match_oracle_mesh8(self, mesh8):
        """Under the interpret plane the per-shard state dispatches run the
        tiles kernel (causal routes through the banded layout), so the ring's
        zig-zag diagonal half-blocks exercise row extents end-to-end."""
        q, k, v = _qkv(LQ=64)
        with ops.backend("interpret"), use_level(ExecLevel.O3, mesh8):
            sel = registry.select("flash_attention", q, k, v, causal=True)
            assert sel.name == "ring"
            got = registry.dispatch("flash_attention", q, k, v, causal=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_ring_banded_shards_match_oracle_mesh222(self, mesh222):
        q, k, v = _qkv(LQ=64)
        with ops.backend("interpret"), use_level(ExecLevel.O4, mesh222):
            got = registry.dispatch("flash_attention", q, k, v, causal=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_rich_mask_falls_off_the_ring(self, mesh8):
        """A windowed mask is not ring-expressible: selection degrades to
        the chip block-sparse path under the mesh, numerics unchanged."""
        q, k, v = _qkv(LQ=64)
        spec = MaskSpec(causal=True, window=16)
        with ops.backend("interpret"), use_level(ExecLevel.O3, mesh8):
            sel = registry.select("flash_attention", q, k, v, causal=True,
                                  mask=spec)
            assert sel.name == "blocksparse_interpret"
            got = registry.dispatch("flash_attention", q, k, v, causal=True,
                                    mask=spec)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_oracle(q, k, v, spec)),
                                   rtol=1e-5, atol=1e-5)

    def test_ring_trivial_mask_still_rides_the_ring(self, mesh8):
        q, k, v = _qkv(LQ=64)
        with use_level(ExecLevel.O3, mesh8):
            sel = registry.select("flash_attention", q, k, v, causal=True,
                                  mask=MaskSpec(causal=True))
            assert sel.name == "ring"


# ---------------------------------------------------------------------------
# model integration: configs carry the spec
# ---------------------------------------------------------------------------

class TestModelIntegration:
    def _cfg(self, **kw):
        from repro.configs.base import ModelConfig
        return ModelConfig(name="t", family="dense", num_layers=1,
                           d_model=32, vocab_size=64, num_heads=4,
                           num_kv_heads=2, head_dim=8, d_ff=64,
                           dtype="float32", **kw)

    def test_mask_spec_lowering(self):
        assert self._cfg().attn_mask_spec() is None
        spec = self._cfg(attn_window=16,
                         attn_global_tokens=(0, 1)).attn_mask_spec()
        assert spec == MaskSpec(causal=True, window=16,
                                global_tokens=(0, 1))
        assert self._cfg(attn_global_tokens=(0,)).attn_mask_spec() == \
            MaskSpec(causal=True, global_tokens=(0,))

    def test_windowed_config_changes_attention(self):
        from repro.models.attention import attention_apply, attention_init
        from repro.models.layers import rope
        cfg_w = self._cfg(attn_window=16)
        cfg_d = self._cfg()
        p = attention_init(jax.random.PRNGKey(0), cfg_d)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 64, 32)),
                        jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
        cos, sin = rope(pos, cfg_d.head_dim, cfg_d.rope_theta)
        out_w = attention_apply(x, p, cfg_w, cos, sin)
        out_d = attention_apply(x, p, cfg_d, cos, sin)
        assert np.all(np.isfinite(np.asarray(out_w)))
        assert not np.allclose(np.asarray(out_w), np.asarray(out_d))
