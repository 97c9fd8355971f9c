"""Each cell's jitted programs compiled at their real sizes for a described
TPU v5e chip -- no chip needed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench.harness import ROOT, load_module

gen = load_module(ROOT, "generators", "stencil")
cg = load_module(ROOT, "entries", "cg")

#: one v5e chip's HBM: a cell's program has to fit it
HBM_BYTES = 16 * 2 ** 30


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def traffic(name):
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture
def tpu_plane(monkeypatch, no_persistent_cache):
    """Registry selection as on the chip: the platform reads 'tpu' and the
    Pallas plane is requested."""
    from repro.core import registry

    real = registry.select_context
    monkeypatch.setattr(registry, "select_context",
                        lambda: dataclasses.replace(real(), platform="tpu"))
    with registry.use_backend("pallas"):
        yield


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def _layout(topo):
    return (gen.Operator(config("stencil7_256")),
            SingleDeviceSharding(topo.devices[0]))


def test_generator_compiles_at_real_size(topo, no_persistent_cache):
    op, one = _layout(topo)
    keys = jax.ShapeDtypeStruct(gen.direction_keys(0, op.grid,
                                                   op.points).shape,
                                jnp.uint32, sharding=one)
    _fits(gen.maker(op.grid, op.points, one).lower(keys).compile())


def test_cg_program_compiles_at_real_size(topo, tpu_plane):
    op, one = _layout(topo)
    t = traffic("cg")
    d = jax.ShapeDtypeStruct((len(op.offsets), op.n), jnp.float32,
                             sharding=one)
    b = jax.ShapeDtypeStruct((op.n,), jnp.float32, sharding=one)
    with cg.level():
        compiled = jax.jit(cg.program(op.offsets, op.n, t["rtol"],
                                      t["max_iters"])).lower(d, b).compile()
    assert "tpu_custom_call" in _fits(compiled).as_text()   # the DIA kernel


def test_spmv_dia_kernel_compiles_at_real_size(topo, tpu_plane):
    from repro.kernels import ops

    op, one = _layout(topo)
    d = jax.ShapeDtypeStruct((len(op.offsets), op.n), jnp.float32,
                             sharding=one)
    x = jax.ShapeDtypeStruct((op.n,), jnp.float32, sharding=one)
    compiled = jax.jit(lambda d, x: ops.spmv_dia(d, op.offsets, x)).lower(
        d, x).compile()
    assert "tpu_custom_call" in _fits(compiled).as_text()
