"""Mesh-adaptive sharding helpers used by models and the train/serve steps.

All model code names logical axes:  BATCH (data parallel), MODEL (tensor/
expert parallel).  At O3 the mesh is (data, model); at O4 (pod, data, model).
``batch_axes()`` resolves BATCH to whichever data axes exist, so the same
model code lowers on both meshes (and on no mesh at all for CPU smoke tests —
every helper degrades to a no-op then).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["active_mesh", "batch_axes", "bspec", "constrain", "spec",
           "named", "MODEL"]

MODEL = "model"


def active_mesh() -> Optional[Any]:
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def batch_axes(mesh=None) -> tuple[str, ...]:
    m = mesh or active_mesh()
    if m is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in m.axis_names)


def bspec(mesh=None):
    """The PartitionSpec entry for a batch dimension on the active mesh."""
    axes = batch_axes(mesh)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def spec(*entries) -> P:
    """Build a PartitionSpec, resolving the sentinel 'batch' to bspec()."""
    resolved = []
    for e in entries:
        if e == "batch":
            resolved.append(bspec())
        elif e == MODEL:
            m = active_mesh()
            resolved.append(MODEL if (m is not None and MODEL in m.axis_names)
                            else None)
        else:
            resolved.append(e)
    return P(*resolved)


def constrain(x: jax.Array, *entries) -> jax.Array:
    """with_sharding_constraint that no-ops without a mesh in context."""
    if active_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec(*entries))


def named(mesh: Mesh, *entries) -> NamedSharding:
    axes = set(mesh.axis_names)
    resolved = []
    for e in entries:
        if e == "batch":
            b = tuple(a for a in ("pod", "data") if a in axes)
            resolved.append(b if len(b) > 1 else (b[0] if b else None))
        elif isinstance(e, str) and e not in axes:
            resolved.append(None)
        else:
            resolved.append(e)
    return NamedSharding(mesh, P(*resolved))
