"""Execution levels: the ArBB runtime-retargeting story, scaled out.

Paper §3: "ArBB supports two different optimisation levels, which can be
specified at run-time by setting the environment variable ARBB_OPT_LEVEL to O2
for vectorisation on a single core or to O3 for vectorisation and usage of
multiple cores ... ARBB_NUM_CORES can then be used to specify the number of
threads."

The defining property is that the *program text never changes* — only the
execution level does.  We keep that property and extend the ladder past the
paper's shared-memory ceiling (its §4: "ArBB is limited to shared memory
systems"):

    O2  — one chip: XLA vectorisation only (paper's O2).
    O3  — one pod:  containers sharded over a ``(data, model)`` mesh
          (paper's O3; mesh size plays the role of ARBB_NUM_CORES).
    O4  — multi-pod: ``(pod, data, model)`` mesh — the beyond-paper level;
          cross-pod collectives become hierarchical.

Levels are process-local context (like ArBB's env vars, but scoped), consumed
by :func:`repro.core.closure.call`.  ``ARBB_OPT_LEVEL`` / ``ARBB_NUM_CORES``
env vars are honoured at import for CLI parity with the paper.
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import os
import threading
from typing import Iterator, Optional

import jax


__all__ = ["ExecLevel", "ExecContext", "use_level", "current", "default_mesh_for"]


class ExecLevel(enum.IntEnum):
    O2 = 2  # single chip, vectorise only
    O3 = 3  # single pod, (data, model) mesh
    O4 = 4  # multi-pod, (pod, data, model) mesh


@dataclasses.dataclass(frozen=True)
class ExecContext:
    level: ExecLevel
    mesh: Optional[jax.sharding.Mesh] = None

    @property
    def is_distributed(self) -> bool:
        return self.level >= ExecLevel.O3 and self.mesh is not None


_state = threading.local()


def _default_level() -> ExecLevel:
    env = os.environ.get("ARBB_OPT_LEVEL", "O2").upper().lstrip("O")
    try:
        return ExecLevel(int(env))
    except ValueError:
        return ExecLevel.O2


def current() -> ExecContext:
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        ctx = ExecContext(_default_level(), None)
        _state.ctx = ctx
    return ctx


def default_mesh_for(level: ExecLevel) -> Optional[jax.sharding.Mesh]:
    """Build a mesh from whatever devices exist (honours ARBB_NUM_CORES)."""
    if level == ExecLevel.O2:
        return None
    devices = jax.devices()
    n = int(os.environ.get("ARBB_NUM_CORES", len(devices)))
    n = max(1, min(n, len(devices)))
    if level == ExecLevel.O3:
        return jax.make_mesh((n, 1), ("data", "model"),
                             (jax.sharding.AxisType.Auto,) * 2)
    # O4: split off a pod axis when device count allows.
    pods = 2 if n % 2 == 0 and n >= 2 else 1
    return jax.make_mesh((pods, n // pods, 1), ("pod", "data", "model"),
                         (jax.sharding.AxisType.Auto,) * 3)


@contextlib.contextmanager
def use_level(level: ExecLevel, mesh: Optional[jax.sharding.Mesh] = None) -> Iterator[ExecContext]:
    """Scoped execution level (the ArBB env-var knob, made composable)."""
    if mesh is None and level >= ExecLevel.O3:
        mesh = default_mesh_for(level)
    prev = getattr(_state, "ctx", None)
    ctx = ExecContext(ExecLevel(level), mesh)
    _state.ctx = ctx
    try:
        yield ctx
    finally:
        _state.ctx = prev
