"""Shared fixtures.

The suite runs with 8 forced host-platform CPU devices (the XLA flag below
MUST be set before the first jax import — jax locks the device count at
init) so the O3/O4 mesh paths are exercisable on CPU CI: mesh-scoped
registry variants, shard_map SpMV/matmul/FFT, and the distributed CG all
run for real against the fake-device mesh.  Single-chip tests are
unaffected — with no ambient mesh, computation stays on device 0 and the
registry's chip variants select exactly as before.  launch/dryrun.py (run
as its own process) still forces its own 512 placeholder devices.
"""
import os

# Before any jax import (pytest imports conftest first).  An explicit
# caller-provided count wins — e.g. a CI shard pinning a different width.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import functools

import numpy as np
import pytest


@functools.lru_cache(maxsize=1)
def _interpret_grad_broken() -> bool:
    """Probe whether reverse-mode autodiff through a pallas_call works.  On
    jax 0.9 it does not (linearization fails: pallas_call has no transpose
    rule, and the flash kernel defines no custom VJP), so the train-step
    tests cannot run when ``REPRO_KERNELS=interpret`` routes attention
    through the interpret kernel.  Probing — rather than pinning a version
    — means the skip disappears by itself once the kernel is
    differentiable."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def f(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x).sum()

    try:
        jax.grad(f)(jnp.ones((8,), jnp.float32))
        return False
    except Exception:
        return True


def _arch_differentiates_interpret_kernel(arch: str) -> bool:
    """Only archs with attention reach the interpret flash kernel inside
    value_and_grad (mamba2's SSM path never dispatches it)."""
    from repro.configs import get_config

    return getattr(get_config(arch), "num_heads", 0) > 0


#: non-parametrised tests that also differentiate the interpret flash
#: kernel inside a train step (same limitation as the arch smokes).
_GRAD_TRAIN_TESTS = (
    "test_train_step_runs_under_degenerate_mesh",
    "test_loss_decreases_on_learnable_task",
    "test_grad_accumulation_matches_full_batch",
    "test_restart_resumes_bit_exact",
)


def pytest_collection_modifyitems(config, items):
    """Under ``REPRO_KERNELS=interpret`` (./test.sh's default), skip the
    train-step smoke tests that would differentiate an interpret-mode
    pallas_call — with the reason stated — so the suite is green in every
    plane mode."""
    if os.environ.get("REPRO_KERNELS") != "interpret":
        return
    if not _interpret_grad_broken():
        return
    skip = pytest.mark.skip(
        reason="pallas_call is not reverse-mode differentiable (probe "
               "failed); the same train step passes under the default "
               "plane and the kernels' forward paths are still validated "
               "in interpret mode")
    for item in items:
        if any(name in item.nodeid for name in _GRAD_TRAIN_TESTS):
            item.add_marker(skip)
            continue
        if "test_reduced_arch_forward_and_train_step" not in item.nodeid:
            continue
        arch = getattr(getattr(item, "callspec", None), "params", {}).get("arch")
        if arch and _arch_differentiates_interpret_kernel(arch):
            item.add_marker(skip)


class _F32Rng:
    """np.random.Generator facade returning float32 (JAX's default width —
    f64 inputs would silently downcast and break exact-equality asserts)."""

    def __init__(self, seed=0):
        self._rng = np.random.default_rng(seed)

    def standard_normal(self, *a, **k):
        return self._rng.standard_normal(*a, **k).astype(np.float32)

    def integers(self, *a, **k):
        return self._rng.integers(*a, **k)

    def uniform(self, *a, **k):
        return self._rng.uniform(*a, **k).astype(np.float32)


@pytest.fixture(autouse=True)
def _isolate_costmodel(monkeypatch, tmp_path):
    """Point the measured cost model at a per-test temp path.  Selection
    must be deterministic under test: a ``results/costmodel.json`` left
    behind by a local sweep would otherwise re-rank dispatch for every
    selection assertion in the suite (DESIGN.md §11 precedence).  Tests of
    the model itself monkeypatch ``REPRO_COSTMODEL`` again on top."""
    monkeypatch.setenv("REPRO_COSTMODEL", str(tmp_path / "costmodel.json"))


@pytest.fixture
def rng():
    return _F32Rng(0)


@pytest.fixture
def mesh8():
    """(data=8, model=1) mesh over the forced host-platform devices — the
    O3 fixture for scope-aware selection and shard_map numerics tests."""
    import jax

    if jax.device_count() < 8:
        pytest.skip(f"needs 8 devices, have {jax.device_count()} "
                    "(XLA_FLAGS set after jax init?)")
    return jax.make_mesh((8, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture
def mesh222():
    """(pod=2, data=2, model=2) mesh — the O4 fixture: hierarchical
    reduction plans (reduce-scatter intra-pod, all-reduce inter-pod), the
    2-D (data, model) matmul tiling, and pod-aware CG all exercise on it."""
    import jax

    if jax.device_count() < 8:
        pytest.skip(f"needs 8 devices, have {jax.device_count()} "
                    "(XLA_FLAGS set after jax init?)")
    return jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         (jax.sharding.AxisType.Auto,) * 3)
