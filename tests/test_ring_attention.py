"""The sequence-parallel plane (DESIGN.md §10): ring attention as the
mesh-scoped flash variant, on 8 fake devices.

Contracts under test:
  * plan — ``ring_plan`` emits a flat ring over ``data`` on O3 and a
    pod-major ring over ``pod × data`` on O4; the rotation perm and the
    zig-zag sequence layout round-trip;
  * selection — ``flash_attention`` retargets to ``ring`` under
    use_level(O3/O4) with no call-site change, degrades to the chip path
    on a 1-device mesh or an L the ring doesn't divide, and explicit
    ``variant=`` pins either way;
  * numerics — ring == chip flash == XLA oracle for causal and full
    attention, GQA and MQA head layouts, zig-zag and contiguous
    orderings, on both mesh shapes; bf16 stays within 1e-3 of chip;
    gradients (the training step's view) match;
  * integration — the serve engine pins the ambient level at construction
    so prefill selects the ring on every generate() call.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ExecLevel, registry, use_level
from repro.distributed import attention as rattn
from repro.distributed.collectives import ring_plan
from repro.kernels import ref

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8 forced host devices")


def _qkv(B=2, H=4, HK=2, L=64, D=16, dtype=jnp.float32, vscale=1.0, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, H, L, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, HK, L, D)), dtype)
    v = jnp.asarray(vscale * rng.standard_normal((B, HK, L, D)), dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# the ring plan
# ---------------------------------------------------------------------------

class TestRingPlan:
    def test_flat_ring_on_o3(self, mesh8):
        plan = ring_plan(mesh8)
        assert plan.axes == ("data",)
        assert plan.size == 8
        assert plan.perm == tuple((i, (i + 1) % 8) for i in range(8))
        assert plan.schedule() == (("ppermute", ("data",)),) * 7
        assert plan.spec_entry() == "data"

    def test_pod_major_ring_on_o4(self, mesh222):
        plan = ring_plan(mesh222)
        assert plan.axes == ("pod", "data")   # pod-major: ICI hops first
        assert plan.size == 4
        assert plan.perm == ((0, 1), (1, 2), (2, 3), (3, 0))
        assert plan.spec_entry() == ("pod", "data")

    def test_degenerate_mesh_has_no_ring(self):
        mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                              (jax.sharding.AxisType.Auto,) * 2,
                              devices=jax.devices()[:1])
        assert ring_plan(mesh1).size == 1

    def test_zigzag_perm_roundtrip(self):
        got = rattn.zigzag_perm(32, 4)
        assert got is not None
        order, inv = got
        # shard 0 holds half-blocks 0 and 2*4-1 = 7 (one early, one late)
        h = 32 // 8
        np.testing.assert_array_equal(order[:2 * h],
                                      np.r_[0:h, 7 * h:8 * h])
        np.testing.assert_array_equal(order[inv], np.arange(32))
        assert rattn.zigzag_perm(30, 4) is None       # 30 % 8 != 0
        assert rattn.zigzag_perm(32, 1) is None       # no ring


# ---------------------------------------------------------------------------
# scope-aware selection + degradation
# ---------------------------------------------------------------------------

class TestRingSelection:
    def test_ring_selects_under_mesh_chip_without(self, mesh8):
        q, k, v = _qkv()
        assert registry.select("flash_attention", q, k, v,
                               causal=True).scope == "chip"
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("flash_attention", q, k, v,
                                   causal=True).name == "ring"
        assert registry.select("flash_attention", q, k, v,
                               causal=True).scope == "chip"

    def test_ring_selects_on_o4(self, mesh222):
        q, k, v = _qkv()
        with use_level(ExecLevel.O4, mesh222):
            assert registry.select("flash_attention", q, k, v,
                                   causal=True).name == "ring"

    def test_indivisible_length_degrades_to_chip(self, mesh8):
        # causal needs 2*8 = 16 half-blocks; 40 % 16 != 0
        q, k, v = _qkv(L=40)
        with use_level(ExecLevel.O3, mesh8):
            sel = registry.select("flash_attention", q, k, v, causal=True)
            assert sel.scope == "chip"
            got = registry.dispatch("flash_attention", q, k, v, causal=True)
        chip = registry.dispatch("flash_attention", q, k, v, causal=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(chip))

    def test_one_device_mesh_degrades_to_chip(self):
        mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                              (jax.sharding.AxisType.Auto,) * 2,
                              devices=jax.devices()[:1])
        q, k, v = _qkv()
        with use_level(ExecLevel.O3, mesh1):
            sel = registry.select("flash_attention", q, k, v, causal=True)
            assert sel.scope == "chip"
            got = registry.dispatch("flash_attention", q, k, v, causal=True)
        chip = registry.dispatch("flash_attention", q, k, v, causal=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(chip))

    def test_explicit_variant_pins(self, mesh8):
        q, k, v = _qkv()
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("flash_attention", q, k, v, causal=True,
                                   variant="xla").name == "xla"
            assert registry.select("flash_attention", q, k, v, causal=True,
                                   variant="ring").name == "ring"
            pinned = registry.dispatch("flash_attention", q, k, v,
                                       causal=True, variant="xla")
        chip = registry.dispatch("flash_attention", q, k, v, causal=True,
                                 variant="xla")
        np.testing.assert_array_equal(np.asarray(pinned), np.asarray(chip))


# ---------------------------------------------------------------------------
# numerics: ring == chip flash == oracle
# ---------------------------------------------------------------------------

class TestRingNumerics:
    @pytest.mark.parametrize("heads", [(4, 2), (4, 1), (4, 4)],
                             ids=["gqa", "mqa", "mha"])
    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    def test_ring_matches_oracle_mesh8(self, mesh8, heads, causal):
        H, HK = heads
        q, k, v = _qkv(H=H, HK=HK)
        want = ref.attention_ref(q, k, v, causal=causal)
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("flash_attention", q, k, v,
                                   causal=causal).name == "ring"
            got = registry.dispatch("flash_attention", q, k, v,
                                    causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        chip = registry.dispatch("flash_attention", q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(chip),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    def test_ring_matches_oracle_mesh222(self, mesh222, causal):
        q, k, v = _qkv()
        want = ref.attention_ref(q, k, v, causal=causal)
        with use_level(ExecLevel.O4, mesh222):
            got = registry.dispatch("flash_attention", q, k, v,
                                    causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_zigzag_and_contiguous_orderings_agree(self, mesh8):
        q, k, v = _qkv()
        want = ref.attention_ref(q, k, v, causal=True)
        with use_level(ExecLevel.O3, mesh8):
            zz = rattn.ring_attention(q, k, v, causal=True, order="zigzag")
            ct = rattn.ring_attention(q, k, v, causal=True,
                                      order="contiguous")
        np.testing.assert_allclose(np.asarray(zz), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ct), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_causal_gqa_within_1e3_of_chip(self, mesh8):
        """The acceptance shape: bf16 inputs, f32 accumulation — ring and
        chip flash agree to 1e-3 on a causal GQA problem."""
        q, k, v = _qkv(dtype=jnp.bfloat16, vscale=0.1)
        chip = registry.dispatch("flash_attention", q, k, v, causal=True)
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("flash_attention", q, k, v,
                                   causal=True).name == "ring"
            got = registry.dispatch("flash_attention", q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(chip, np.float32), atol=1e-3)

    def test_gradients_match_chip(self, mesh8):
        """The training step differentiates through the ring: dL/dq of the
        sharded formulation matches the chip kernel's."""
        import os

        from conftest import _interpret_grad_broken
        if os.environ.get("REPRO_KERNELS") == "interpret" \
                and _interpret_grad_broken():
            pytest.skip("pallas_call is not reverse-mode differentiable "
                        "(probe failed); the ring's grad path is "
                        "validated under the default plane")
        q, k, v = _qkv(B=1, H=2, HK=1, L=32, D=8)

        def loss(q, variant=None):
            out = registry.dispatch("flash_attention", q, k, v, causal=True,
                                    variant=variant)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        g_chip = jax.grad(loss)(q, "xla")
        with use_level(ExecLevel.O3, mesh8):
            assert registry.select("flash_attention", q, k, v,
                                   causal=True).name == "ring"
            g_ring = jax.grad(loss)(q)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_chip),
                                   rtol=1e-4, atol=1e-4)

    def test_ring_without_mesh_raises(self):
        q, k, v = _qkv()
        with pytest.raises(RuntimeError, match="ambient O3/O4 mesh"):
            rattn.ring_attention(q, k, v, causal=True)


# ---------------------------------------------------------------------------
# the state op the ring dispatches per shard
# ---------------------------------------------------------------------------

class TestFlashState:
    @pytest.mark.parametrize("causal", [True, False])
    def test_state_matches_plain_and_merges(self, causal):
        q, k, v = _qkv(L=32)
        o, m, l = registry.dispatch("flash_attention_state", q, k, v,
                                    causal=causal)
        plain = registry.dispatch("flash_attention", q, k, v, causal=causal,
                                  variant="xla")
        np.testing.assert_allclose(np.asarray(o), np.asarray(plain),
                                   rtol=1e-5, atol=1e-5)
        assert m.shape == l.shape == q.shape[:3]
        # two half-panel states merge to the whole-panel state (the
        # cross-hop algebra of the ring, non-causal: order-free)
        if not causal:
            half = 16
            s1 = rattn._as_state(*registry.dispatch(
                "flash_attention_state", q, k[:, :, :half], v[:, :, :half],
                causal=False))
            s2 = rattn._as_state(*registry.dispatch(
                "flash_attention_state", q, k[:, :, half:], v[:, :, half:],
                causal=False))
            mm, ll, acc = rattn._merge(s1, s2)
            merged = acc / jnp.maximum(ll, 1e-30)[..., None]
            np.testing.assert_allclose(np.asarray(merged),
                                       np.asarray(plain, np.float32),
                                       rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model integration: attention_apply retargets with no call-site change
# ---------------------------------------------------------------------------

class TestAttentionApply:
    def test_training_attention_rides_the_ring(self, mesh8):
        """The acceptance contract: attention_apply (the training / prefill
        path) selects the ring under use_level(O3) purely from the ambient
        SelectContext — same program text, same numbers as chip."""
        from repro.configs.base import ModelConfig
        from repro.models import attention as attn
        from repro.models.layers import rope

        cfg = ModelConfig(name="ringattn", family="dense", num_layers=1,
                          d_model=32, vocab_size=64, num_heads=4,
                          num_kv_heads=2, head_dim=8, d_ff=64,
                          dtype="float32", param_dtype="float32",
                          remat=False)
        p = attn.attention_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32),
                              jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
        cos, sin = rope(pos, cfg.head_dim, cfg.rope_theta)
        chip = attn.attention_apply(x, p, cfg, cos, sin)
        with use_level(ExecLevel.O3, mesh8):
            # the dispatch the apply path makes resolves to the ring here
            q, k, v = _qkv(B=2, H=4, HK=2, L=64, D=8)
            assert registry.select("flash_attention", q, k, v,
                                   causal=True).name == "ring"
            ring = attn.attention_apply(x, p, cfg, cos, sin)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(chip),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# serve integration: prefill rides the ring, decode stays chip-local
# ---------------------------------------------------------------------------

class TestServePrefill:
    def test_engine_pins_ambient_level_for_prefill(self, mesh8):
        from repro.configs.base import ModelConfig
        from repro.models.lm import LM
        from repro.serve import Engine, SamplingParams

        cfg = ModelConfig(name="ringserve", family="dense", num_layers=2,
                          d_model=32, vocab_size=64, num_heads=4,
                          num_kv_heads=2, head_dim=8, d_ff=64,
                          dtype="float32", param_dtype="float32",
                          remat=False)
        lm = LM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        # prompt length divisible by 2*ring: the prefill shards the ring
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        chip_engine = Engine(lm, params, max_len=48,
                             sampling=SamplingParams(greedy=True))
        chip_out = chip_engine.generate(prompts, max_new_tokens=4)
        with use_level(ExecLevel.O3, mesh8):
            ring_engine = Engine(lm, params, max_len=48,
                                 sampling=SamplingParams(greedy=True))
            # the prefill-shaped dispatch selects the ring in this context
            q, k, v = _qkv(L=32, D=8)
            assert registry.select("flash_attention", q, k, v,
                                   causal=True).name == "ring"
        assert ring_engine.active_level.mesh is mesh8
        # generate() OUTSIDE the context: the engine re-enters the pinned
        # level for prefill; greedy output matches the chip engine
        ring_out = ring_engine.generate(prompts, max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(ring_out),
                                      np.asarray(chip_out))
