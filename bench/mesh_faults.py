#!/usr/bin/env python3
"""Faults planted underneath the mesh CG's timed path (``hpcg27_x4.cg50``),
beside those of ``bench/faults.py``, to show that the check which decides
``correct`` fails them.  Each is a context manager that patches the
program while it is active; trace the timed program inside it
(``jax.clear_caches()`` first).  The benchmark's own runs plant nothing.

    python3 bench/mesh_faults.py --workload hpcg27_x4.cg50 \
        --fault exchange_left_out:16 ...

runs ``bench/readings.py`` with these faults added to its own.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
from unittest import mock

__all__ = ["FAULTS", "planted"]

#: the iteration the early stop ends a 50-iteration set at
EARLY_STOP_ITERS = 45


@contextlib.contextmanager
def _early_stop():
    """Each set stops after ``EARLY_STOP_ITERS`` iterations."""
    from repro.numerics import solvers

    real = solvers.cg_solve

    def broken(a, b, *, max_iters, **k):
        return real(a, b, max_iters=min(max_iters, EARLY_STOP_ITERS), **k)

    with mock.patch.object(solvers, "cg_solve", broken):
        yield


@contextlib.contextmanager
def _exchange_left_out():
    """The halo exchange returns zeros: each shard's SpMV reads its own
    rows only, as if its neighbours held none."""
    import jax.numpy as jnp
    from repro.distributed import collectives

    def zeros(self, x, rows):
        return (jnp.zeros((rows,), x.dtype),) * 2

    with mock.patch.object(collectives.ReducePlan, "halo", zeros):
        yield


@contextlib.contextmanager
def _entry_altered():
    """One entry of each set's x off by 1."""
    from repro.core import unwrap, wrap
    from repro.numerics import solvers

    real = solvers.cg_solve

    def broken(a, b, **k):
        res = real(a, b, **k)
        return dataclasses.replace(res, x=wrap(unwrap(res.x).at[5].add(1.0)))

    with mock.patch.object(solvers, "cg_solve", broken):
        yield


FAULTS = {
    "early_stop_45": _early_stop,
    "entry_altered": _entry_altered,
    "exchange_left_out": _exchange_left_out,
}


def planted(name: str):
    """The context manager that plants fault ``name``."""
    return FAULTS[name]()


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != os.path.dirname(
                       os.path.abspath(__file__))]
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import faults, readings

    faults.FAULTS.update(FAULTS)
    sys.exit(readings.main())
