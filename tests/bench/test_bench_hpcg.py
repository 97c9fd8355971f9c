"""The HPCG cell (``hpcg27_x4.cg50``): its generator against HPCG's
definition and its plain reference against itself, its check on 4 CPU
devices at a small grid (sound runs pass; the bfloat16 control and each
planted fault fail), its work counted by hand, its readers, and its timed
program compiled for a described v5e:2x2 at the real size.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import harness, mesh_faults, peaks
from bench import trace as trace_mod
from bench.harness import ROOT, load_module

gen = load_module(ROOT, "generators", "hpcg")
stencil = load_module(ROOT, "generators", "stencil")
cg_sets = load_module(ROOT, "entries", "cg_sets")

CELL = "hpcg27_x4.cg50"
SEED = 2 ** 31 + 99
#: 4 shards of 32,768 rows (max|offset| 1,057); 50 iterations leave x
#: short of the solution, so an early stop shows
SMALL = [32, 32, 128]
HBM_BYTES = 16 * 2 ** 30

pytestmark = pytest.mark.skipif(jax.device_count() < 4,
                                reason="needs 4 forced host devices")


def config(name="hpcg27_x4", **changes):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return dict(json.load(f), **changes)


def hpcg_dense(grid):
    """HPCG's operator built point by point: 26 on the diagonal, -1 for
    each of the up to 26 neighbours inside the grid."""
    nx, ny, nz = grid
    n = nx * ny * nz
    a = np.zeros((n, n))
    for z in range(nz):
        for y in range(ny):
            for x in range(nx):
                i = x + nx * (y + ny * z)
                for dz in (-1, 0, 1):
                    for dy in (-1, 0, 1):
                        for dx in (-1, 0, 1):
                            u, v, w = x + dx, y + dy, z + dz
                            if 0 <= u < nx and 0 <= v < ny and 0 <= w < nz:
                                a[i, u + nx * (v + ny * w)] = -1.0
                a[i, i] = 26.0
    return a


def as_dense(diags, offs):
    diags = np.asarray(diags, np.float64)
    n = diags.shape[1]
    a = np.zeros((n, n))
    for d, off in enumerate(offs):
        for i in range(n):
            if 0 <= i + off < n:
                a[i, i + off] = diags[d, i]
    return a


@pytest.fixture(scope="module")
def tiny():
    """HPCG's operator on a 4 x 3 x 8 grid in 4 z-slabs of 24 rows."""
    return gen.Operator(config(grid=[4, 3, 8]), SEED, jax.devices()[:4])


def test_cell_is_found_by_name():
    s = harness.spec(CELL)
    assert s.chips == 4 and s.traffic["entry"] == "cg_sets"
    assert s.config["generator"] == "hpcg" and s.config["points"] == 27
    assert s.config["grid"] == [360, 360, 1440]
    assert s.config["process_grid"] == [1, 1, 4]
    assert set(s.cell["limits"]) == {"x_err"}
    assert {m["name"] for m in s.end_to_end} == {"solve_ms", "setup_s"}
    assert {m["name"] for m in s.per_layer} == {
        "cg_iter_us.cg50", "spmv_dia_roofline.cg50", "mfu.cg50",
        "device_idle_share.cg50", "collective_exposed_share.cg50",
        "exchange_kb_per_iter"}


def test_diagonals_are_hpcgs_operator(tiny):
    """Made by rows on 4 devices, the diagonals are HPCG's operator; each
    shard holds its own rows."""
    assert tiny.diags.sharding.spec == P(None, gen.AXIS)
    np.testing.assert_array_equal(as_dense(tiny.diags, tiny.offsets),
                                  hpcg_dense(tiny.grid))


def test_reference_halo_spmv_matches_whole_vector_spmv(tiny):
    x = jnp.asarray(np.random.default_rng(1).standard_normal(tiny.n),
                    jnp.float32)
    x = jax.device_put(x, NamedSharding(tiny.mesh, P(gen.AXIS)))
    got = jax.jit(tiny.spmv)(tiny.diags, x)
    want = stencil.spmv(jnp.asarray(np.asarray(tiny.diags)), tiny.offsets,
                        jnp.asarray(np.asarray(x)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


def test_right_hand_sides_are_a_times_seeded_x_star(tiny):
    keys = stencil.vector_keys(SEED, 2)
    rows = np.arange(tiny.n, dtype=np.int32)
    a = hpcg_dense(tiny.grid)
    for j, b in enumerate(tiny.rhs(SEED, 2)):
        star = 1.0 + stencil.normal(keys[j, 0], keys[j, 1], rows, xp=np)
        np.testing.assert_allclose(np.asarray(b), a @ star, rtol=1e-5,
                                   atol=1e-4)


def _run(monkeypatch, control=False):
    s = harness.spec(CELL)
    s = dataclasses.replace(
        s, config=dict(s.config, grid=SMALL),
        traffic={k: v for k, v in s.traffic.items() if k != "expect"})
    monkeypatch.setattr(harness, "_enable_compile_cache", lambda root: None)
    return harness.run(CELL, SEED, 0.2, False, spec_=s,
                       require_accelerator=False, control=control,
                       log=lambda line: None)


def test_sound_run_is_correct(monkeypatch):
    out = _run(monkeypatch)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


def test_control_in_bfloat16_is_not_correct(monkeypatch):
    out = _run(monkeypatch, control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(mesh_faults.FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    jax.clear_caches()          # no program traced before the fault
    try:
        with mesh_faults.planted(fault):
            out = _run(monkeypatch)
    finally:
        jax.clear_caches()
    assert not out["correct"], out["checks"]


def test_work_counts_by_hand():
    """Per chip: 46,656,000 rows; an iteration moves (27 + 2) * 4 B a row
    for the SpMV and 9 * 4 for the vector passes, 152 B a row, and does
    2 FLOPs a stored non-zero (a quarter of them) plus 10 a row."""
    op = gen.Operator(config())
    rows = 360 * 360 * 360
    assert op.n_local == rows == 46_656_000
    assert op.max_offset == 360 * 360 + 360 + 1 == 129_961
    assert op.spmv_bytes == 29 * 4 * rows
    # in-grid neighbours of each step, summed by hand over the 26 steps:
    # the main diagonal plus, per step, the points whose neighbour is in
    nnz = 360 * 360 * 1440
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                if (dx, dy, dz) != (0, 0, 0):
                    nnz += (360 - abs(dx)) * (360 - abs(dy)) \
                        * (1440 - abs(dz))
    assert op.nnz == nnz
    w = cg_sets.work(op, 50)
    assert w.hbm_bytes == 50 * 152 * rows
    assert w.flops == 50 * (2 * nnz / 4 + 10 * rows)
    # 7.09 GB an iteration at 819 GB/s: the HBM term binds
    assert peaks.least_seconds(w, peaks.lookup("TPU v5 lite")) == \
        pytest.approx(50 * 152 * rows / 819e9)


def test_readers_of_the_new_metrics():
    """``collective_exposed_share``: a collective's time with nothing else
    on its device, averaged over the planes; ``exchange_kb_per_iter``:
    the stats' bytes in kB, absent where the program has no gauge."""
    exposed = harness.reader(ROOT, "collective_exposed_share.cg50")
    kb = harness.reader(ROOT, "exchange_kb_per_iter")
    ops = {"/device:TPU:0": [(0, 40, "fusion.1"),
                             (30, 60, "collective-permute-done.1")],
           "/device:TPU:1": [(0, 100, "fusion.1"),
                             (50, 70, "psum.3")]}
    tr = trace_mod.Trace(device_ops=ops, host=[], window=(0, 100))
    rec = harness.Record(spec=None, calls=1, window_s=1e-7, dispatch_ns=[],
                         stats={"exchange_bytes_per_iter": 1_039_688.0},
                         work=None, trace=tr, planes=sorted(ops))
    assert exposed.read(rec) == pytest.approx(100.0 * (20 + 0) / 2 / 100)
    assert kb.read(rec) == pytest.approx(1039.688)
    rec.stats = {}
    assert kb.read(rec) is None


# ---------------------------------------------------------------------------
# the timed program, compiled for a described v5e:2x2 at the real size
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def tpu_plane(monkeypatch):
    """Registry selection as on the chip, and no persistent cache (a
    compile for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.core import registry

    real = registry.select_context
    monkeypatch.setattr(registry, "select_context",
                        lambda: dataclasses.replace(real(), platform="tpu"))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with registry.use_backend("pallas"):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _loop_body(hlo):
    """The CG while loop's body and every computation it calls."""
    comps = dict(re.findall(r"^(?:ENTRY )?%([\w.-]+) .*?\{\n(.*?)^\}",
                            hlo, re.M | re.S))

    def called(name):
        seen, todo = set(), [name]
        while todo:
            c = todo.pop()
            if c not in seen and c in comps:
                seen.add(c)
                todo += re.findall(
                    r"(?:calls|to_apply|body|condition)=%([\w.-]+)", comps[c])
        return "\n".join(comps[c] for c in seen)

    bodies = [called(b) for b in re.findall(r"while\(.*?\bbody=%([\w.-]+)",
                                            hlo)]
    return next(b for b in bodies if "tpu_custom_call" in b)


def test_timed_program_fits_a_v5e_2x2(topo, tpu_plane):
    """The cell's timed program at 360^3 rows a chip fits 16 GiB a chip;
    its loop exchanges halos and runs the DIA kernel, with no all-gather
    and no pad."""
    op = gen.Operator(config())
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(4, 1),
                             ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    d = jax.ShapeDtypeStruct((27, op.n), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "data")))
    b = jax.ShapeDtypeStruct((op.n,), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    with cg_sets.level(mesh):
        compiled = jax.jit(cg_sets.program(op.offsets, op.n, 50)).lower(
            d, b).compile()
    m = compiled.memory_analysis()
    used = (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)
    print(f"hpcg27_x4 timed program: {used:,} B a chip (arguments "
          f"{m.argument_size_in_bytes:,}, temporaries "
          f"{m.temp_size_in_bytes:,})")
    assert used < HBM_BYTES, used
    body = _loop_body(compiled.as_text())
    assert "collective-permute" in body
    assert "all-gather" not in body and "pad(" not in body
