"""The trace reduction (bench/trace.py) on synthetic events and on a short
trace recorded on a TPU v5e from the ``stencil7_256.spmv_dia`` cell."""
import os

import pytest

from bench import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "spmv_dia.xplane.pb")


def test_union_merges_overlaps_and_drops_empty():
    ev = [(5, 9, "b"), (0, 3, "a"), (2, 4, "c"), (9, 12, "d"), (7, 7, "e")]
    assert trace.union(ev) == [(0, 4), (5, 12)]
    assert trace.total(ev) == 11


def test_busy_is_clipped_to_the_window():
    ops = [(0, 10, "a"), (20, 30, "b"), (25, 40, "c")]
    assert trace.busy_ns(ops, (5, 35)) == 5 + 15


def test_idle_gaps_named_by_the_shortest_covering_host_event():
    ops = [(10, 20, "k"), (30, 40, "k"), (60, 70, "k")]
    host = [(0, 100, "bench.window"), (20, 31, "bench.call"),
            (40, 60, "bench.wait"), (45, 50, "python")]
    gaps = trace.idle_gaps(ops, host, (0, 100))
    assert gaps == [(0, 10, "bench.window"), (20, 30, "bench.call"),
                    (40, 60, "bench.wait"), (70, 100, "bench.window")]


def test_idle_gap_without_host_event_is_unnamed():
    assert trace.idle_gaps([(0, 5, "k")], [], (0, 10)) == [(5, 10, "")]


def test_per_op_sums_durations_by_name():
    ops = [(0, 4, "fusion"), (4, 10, "kernel"), (10, 13, "fusion"),
           (50, 60, "kernel")]
    assert trace.per_op(ops, (0, 55)) == {"fusion": 7, "kernel": 11}


def test_collective_exposure_counts_only_uncovered_time():
    ops = [(0, 10, "all-gather-start.1"), (4, 8, "fusion.2"),
           (20, 30, "all-reduce.3"), (30, 35, "kernel")]
    # all-gather: 10 long, 4 of it covered; all-reduce: 10, none covered
    assert trace.exposed_ns(ops, (0, 40)) == 6 + 10
    assert trace.exposed_ns([(0, 5, "fusion")], (0, 10)) == 0


@pytest.mark.parametrize("name,want", [
    ("all-gather.3", True), ("all-reduce-start", True),
    ("collective-permute-done.1", True), ("fusion.12", False),
    ("spmv_dia_kernel", False)])
def test_is_collective(name, want):
    assert trace.is_collective(name) is want


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.fail(f"recorded trace missing: {RECORDED}")
    return trace.load(RECORDED)


def test_recorded_trace_has_one_tpu_and_a_window(recorded):
    assert list(recorded.device_ops) == ["/device:TPU:0"]
    assert recorded.window_ns > 0
    ops = recorded.device_ops["/device:TPU:0"]
    inside = trace.clip(ops, *recorded.window)
    assert inside, "no device op inside the traced window"


def test_recorded_trace_reduces_to_consistent_numbers(recorded):
    ops = recorded.device_ops["/device:TPU:0"]
    busy = trace.busy_ns(ops, recorded.window)
    per = trace.per_op(ops, recorded.window)
    assert 0 < busy <= recorded.window_ns
    # ops on one chip do not overlap, so the union equals the sum
    assert busy == pytest.approx(sum(per.values()), rel=1e-6)
    gaps = trace.idle_gaps(ops, recorded.host, recorded.window)
    assert sum(e - s for s, e, _ in gaps) == recorded.window_ns - busy
    assert trace.exposed_ns(ops, recorded.window) == 0
    # one pad of x and one kernel launch per call
    assert sorted(per) == ["_spmv_dia_impl.1", "pad.0"]
    kernel = [o for o in ops if o[2] == "_spmv_dia_impl.1"]
    assert len(kernel) == len([o for o in ops if o[2] == "pad.0"]) >= 5


def test_op_name_is_the_hlo_name():
    assert trace.op_name("%pad.0 = f32[8] pad(f32[4] %x)") == "pad.0"
    assert trace.op_name("all-reduce.1") == "all-reduce.1"
