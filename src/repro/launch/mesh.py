"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so that
importing this module never touches jax device state — smoke tests see one
CPU device; only ``dryrun.py`` (which sets ``xla_force_host_platform_
device_count=512`` before any jax import) sees the full fleet.

Axis roles (DESIGN.md §4):
    pod    outer data-parallel axis; gradient reduction across it is
           hierarchical (reduce-scatter intra-pod, all-reduce inter-pod)
    data   intra-pod data parallelism (batch dim)
    model  tensor parallelism (attention heads / ffn / vocab) and expert
           parallelism (MoE experts)
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh


__all__ = ["make_production_mesh", "make_mesh", "describe"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         (jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(data: int = 1, model: int = 1, pod: Optional[int] = None) -> Mesh:
    """Arbitrary mesh for tests/smokes (sized to available devices)."""
    if pod:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                             (jax.sharding.AxisType.Auto,) * 3)
    return jax.make_mesh((data, model), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)


def describe(mesh: Mesh) -> str:
    dims = ", ".join(f"{n}={s}" for n, s in
                     zip(mesh.axis_names, mesh.devices.shape))
    return f"Mesh({dims}; {mesh.devices.size} devices)"
