"""Distribution-layer tests that run on ONE device: partition-rule math
(pure spec reasoning), degenerate-mesh execution, HLO collective parsing,
ZeRO-1 spec extension, MoE group-limited dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_configs
from repro.distributed.partition import (param_specs, zero1_specs,
                                         batch_spec, data_axes)
from repro.launch.mesh import make_mesh
from repro.models.lm import LM
from repro.utils import hlo

ARCHS = [a for a in list_configs() if not a.startswith("euroben")]

POD_AXES = {"data": 16, "model": 16}
MULTIPOD_AXES = {"pod": 2, "data": 16, "model": 16}


def _entry_width(entry, sizes):
    if entry is None:
        return 1
    w = 1
    for a in (entry if isinstance(entry, tuple) else (entry,)):
        w *= sizes[a]
    return w


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("sizes", [POD_AXES, MULTIPOD_AXES],
                         ids=["pod", "multipod"])
def test_param_specs_divisible_on_production_meshes(arch, sizes):
    """Every weight leaf's sharded dims divide evenly on both production
    meshes — the static guarantee behind the dry-run's success."""
    cfg = get_config(arch)
    lm = LM(cfg)
    a_params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    specs = param_specs(a_params)
    flat_p = jax.tree_util.tree_flatten_with_path(a_params)[0]
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for (path, leaf), spec in zip(flat_p, flat_s):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            w = _entry_width(entry, sizes)
            assert dim % w == 0, (
                f"{jax.tree_util.keystr(path)} dim {dim} not divisible "
                f"by {w} (spec {spec})")


def test_param_specs_shard_the_big_leaves():
    """The memory-dominant leaves must not be replicated (TP/EP actually
    applied): every leaf >= 8 MiB carries a 'model' axis — except KV
    projections under the MXU lane floor (deliberately replicated when
    their shards would fall below one 128-lane; see partition.LANE)."""
    cfg = get_config("qwen3-moe-30b-a3b")
    a_params = jax.eval_shape(lambda: LM(cfg).init(jax.random.PRNGKey(0)))
    specs = param_specs(a_params, cfg)
    flat_p = jax.tree_util.tree_flatten_with_path(a_params)[0]
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_p, flat_s):
        size = leaf.size * leaf.dtype.itemsize
        name = jax.tree_util.keystr(path)
        if "'wk'" in name or "'wv'" in name:
            continue                      # lane-floor exemption
        if size >= 8 << 20:
            assert "model" in str(spec), (name, spec)


def test_zero1_extends_sharding():
    cfg = get_config("qwen3-1.7b")
    a_params = jax.eval_shape(lambda: LM(cfg).init(jax.random.PRNGKey(0)))
    mesh = make_mesh(data=1, model=1)     # 1 device: structure-only check
    # emulate a big mesh for the spec math via a fake mesh object
    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (16, 16)
    z = zero1_specs(a_params, FakeMesh)
    base = param_specs(a_params)
    n_more = 0
    for b, zz in zip(jax.tree_util.tree_leaves(base, is_leaf=lambda x: isinstance(x, P)),
                     jax.tree_util.tree_leaves(z, is_leaf=lambda x: isinstance(x, P))):
        if str(b) != str(zz):
            n_more += 1
            assert "data" in str(zz)
    assert n_more > 0


def test_train_step_runs_under_degenerate_mesh():
    """The sharded train path executes on a (1,1) mesh — same code that
    lowers at (16,16); catches constrain/spec bugs cheaply."""
    from repro.configs.base import ModelConfig
    from repro.optim import adamw
    from repro.optim.schedules import constant
    from repro.train import create, make_train_step
    cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                      vocab_size=64, num_heads=4, num_kv_heads=2, head_dim=8,
                      num_experts=4, experts_per_token=2, moe_d_ff=32,
                      capacity_factor=4.0, dtype="float32",
                      param_dtype="float32", remat=False)
    lm = LM(cfg)
    opt = adamw(constant(1e-3))
    state = create(lm, opt, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    mesh = make_mesh(data=1, model=1)
    with jax.sharding.set_mesh(mesh):
        state2, metrics = jax.jit(make_train_step(lm, opt))(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_moe_groups_follow_mesh():
    from repro.models.moe import _default_groups
    assert _default_groups(64) == 1          # no mesh
    mesh = make_mesh(data=1, model=1)
    with jax.sharding.set_mesh(mesh):
        assert _default_groups(64) == 1      # 1-wide data axis


class TestHLOParser:
    HLO = """
HloModule jit_step
%add (x: f32[], y: f32[]) -> f32[] { ... }
ENTRY %main {
  %p0 = f32[256,1024]{1,0} parameter(0)
  %dot.1 = f32[256,1024]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}
  %all-reduce.1 = f32[256,1024]{1,0} all-reduce(%dot.1), channel_id=1, to_apply=%add
  %ag.8 = bf16[512,64]{1,0} parameter(1)
  %all-gather.2 = bf16[512,1024]{1,0} all-gather(%ag.8), dimensions={1}
  %rs.in = f32[64]{0} parameter(2)
  %reduce-scatter.3 = f32[4]{0} reduce-scatter(%rs.in), dimensions={0}
  %cp = f32[8,8]{1,0} collective-permute(%dot.1), source_target_pairs={{0,1}}
  ROOT %t = (f32[256,1024]{1,0}) tuple(%all-reduce.1)
}
"""

    def test_collective_bytes_resolves_operands(self):
        got = hlo.collective_bytes(self.HLO)
        assert got["all-reduce"] == 256 * 1024 * 4
        assert got["all-gather"] == 512 * 64 * 2        # operand, not result
        assert got["reduce-scatter"] == 64 * 4          # operand, not result
        assert got["collective-permute"] == 256 * 1024 * 4   # %dot.1
        assert got["total"] == sum(v for k, v in got.items() if k != "total")

    def test_count_ops(self):
        assert hlo.count_ops(self.HLO, "all-reduce") == 1
        assert hlo.count_ops(self.HLO, "dot") == 1

    def test_real_compiled_module_roundtrip(self):
        """Parser handles a real compiled HLO dump (single-device: zero
        collectives, but instruction grammar must parse)."""
        compiled = jax.jit(lambda x: (x @ x).sum()).lower(
            jnp.ones((64, 64))).compile()
        txt = compiled.as_text()
        sizes = hlo.parse_result_bytes(txt)
        assert len(sizes) > 0
        got = hlo.collective_bytes(txt)
        assert got.get("total", 0) == 0


class TestRooflineModel:
    def test_terms_math(self):
        from repro.utils.roofline import RooflineTerms, TPU_V5E
        t = RooflineTerms(
            arch="a", shape="s", mesh="16x16",
            flops_per_chip=197e12 * 0.010,          # 10 ms of compute
            hbm_bytes_per_chip=819e9 * 0.005,       # 5 ms of HBM
            coll_bytes_per_chip=200e9 * 0.002,      # 2 ms of ICI
            coll_breakdown={}, t_compute=0.010, t_memory=0.005,
            t_collective=0.002, model_flops_total=0.0,
            peak_flops=TPU_V5E.peak_flops, useful_ratio=0.5)
        assert t.dominant == "compute"
        assert t.step_time == pytest.approx(0.010)
        assert t.roofline_fraction == pytest.approx(1.0)
        assert t.mfu_bound == pytest.approx(0.5)

    def test_peaks_table_is_keyed_by_device_kind(self):
        from repro.utils.roofline import TPU_V5E, peaks
        hw = peaks("TPU v5 lite")
        assert hw is TPU_V5E
        assert (hw.peak_flops, hw.hbm_bw, hw.ici_bw) == (197e12, 819e9,
                                                         200e9)
        with pytest.raises(KeyError, match="no published peaks"):
            peaks("cpu")

    def test_model_flops_moe_uses_active(self):
        from repro.utils.roofline import model_flops
        dense = get_config("qwen3-1.7b")
        moe = get_config("qwen3-moe-30b-a3b")
        assert model_flops(moe, 1000) < 6 * moe.param_count() * 1000
        assert model_flops(dense, 1000) == pytest.approx(
            6 * dense.param_count() * 1000)
