"""The check that decides ``correct``, at tiny grids on the CPU: sound runs
pass; the control (the plain reference in bfloat16 in the program's place)
fails; and so does each fault of ``bench/faults.py`` planted underneath the
timed path."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import faults, harness

CELLS = ["stencil7_256.cg", "stencil7_256.spmv_dia"]
SEED = 2 ** 31 + 99


def run(name, monkeypatch, control=False):
    s = harness.spec(name)
    s = dataclasses.replace(
        s, config=dict(s.config, grid=[16, 16, 4]),
        traffic={k: v for k, v in s.traffic.items() if k != "expect"})
    monkeypatch.setattr(harness, "_enable_compile_cache", lambda root: None)
    return harness.run(name, SEED, 0.2, False, spec_=s,
                       require_accelerator=False, control=control,
                       log=lambda line: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, monkeypatch):
    out = run(name, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_control_in_bfloat16_is_not_correct(name, monkeypatch):
    out = run(name, monkeypatch, control=True)
    assert not out["correct"], out["checks"]


CASES = [
    ("stencil7_256.spmv_dia", "answer_altered"),
    ("stencil7_256.spmv_dia", "half_the_rows_left_out"),
    ("stencil7_256.cg", "answer_altered"),
    ("stencil7_256.cg", "solution_altered"),
    ("stencil7_256.cg", "early_stop"),
    ("stencil7_256.cg", "state_unchanged"),
]


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    jax.clear_caches()          # no program traced before the fault
    try:
        with faults.planted(fault):
            out = run(name, monkeypatch)
    finally:
        jax.clear_caches()
    assert not out["correct"], out["checks"]


def test_warm_call_stops_before_the_first_iteration():
    """Set-up warms the timed CG program on b = 0: the same program, no
    solve."""
    s = harness.spec("stencil7_256.cg")
    cg = harness.load_module(harness.ROOT, "entries", "cg")
    cell = cg.build(dict(s.config, grid=[8, 8, 4]), s.traffic, SEED,
                    jax.devices()[:1])
    x, k = cell._solve(jnp.zeros_like(cell.bs[0]))
    assert int(k) == 0 and not jnp.any(x)
    assert int(cell.call(0)[1]) > 0
