"""Faults planted underneath a cell's timed path, to show that the check
which decides ``correct`` fails them.  Each is a context manager that
patches the program while it is active; trace the timed program inside it
(``jax.clear_caches()`` first), as ``readings.py --fault`` and the tests
do.  The benchmark's own runs plant nothing."""
from __future__ import annotations

import contextlib
import dataclasses
from unittest import mock

__all__ = ["FAULTS", "planted"]


def _alter(y):
    """An answer altered where it is made: one entry off by 1."""
    return y.at[5].add(1.0)


def _half(y):
    """Half of the rows left out."""
    return y.at[y.shape[0] // 2:].set(0.0)


@contextlib.contextmanager
def _spmv(fault):
    from repro.kernels import ops

    real = ops.spmv_dia
    with mock.patch.object(ops, "spmv_dia",
                           lambda d, offs, x: fault(real(d, offs, x))):
        yield


def _patch_cg(change_result=None, change_stop=None):
    from repro.core import unwrap, wrap
    from repro.numerics import solvers

    real = solvers.cg_solve

    def broken(a, b, *, stop, **k):
        res = real(a, b, stop=change_stop(stop) if change_stop else stop,
                   **k)
        if change_result:
            res = dataclasses.replace(res, x=wrap(change_result(
                unwrap(res.x))))
        return res
    return mock.patch.object(solvers, "cg_solve", broken)


@contextlib.contextmanager
def _state_unchanged():
    """The solver's loop returns its initial state: x stays 0."""
    from repro.numerics import solvers

    with mock.patch.object(solvers, "arbb_while",
                           lambda cond, body, init: init):
        yield


#: a solve stopped early: at 1e-3 of |b| where the cell asks 1e-6 (the
#: stop is on the squared residual)
EARLY_STOP_FACTOR = (1e-3 / 1e-6) ** 2

FAULTS = {
    "answer_altered": lambda: _spmv(_alter),
    "half_the_rows_left_out": lambda: _spmv(_half),
    "solution_altered": lambda: _patch_cg(change_result=_alter),
    "early_stop": lambda: _patch_cg(
        change_stop=lambda stop: stop * EARLY_STOP_FACTOR),
    "state_unchanged": _state_unchanged,
}


def planted(name: str):
    """The context manager that plants fault ``name``."""
    return FAULTS[name]()
