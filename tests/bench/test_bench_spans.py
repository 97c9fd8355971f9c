"""The readers of the program's dispatch spans (``select_us``,
``invoke_us``, ``runtime_gap_us``) on synthetic traces, on a short trace
recorded on a TPU v5e from the ``stencil7_256.spmv_dia`` cell with the
spans on the profiler's timeline, and on the older recording without them
(a program that has no such spans)."""
import os
import types

import pytest

from bench import harness, spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
WITH_SPANS = os.path.join(DATA, "spmv_dia_spans.xplane.pb")
WITHOUT_SPANS = os.path.join(DATA, "spmv_dia.xplane.pb")
METRICS = ("select_us", "invoke_us", "runtime_gap_us")


def read(metric, tr, planes=("/device:TPU:0",)):
    rec = types.SimpleNamespace(trace=tr, planes=list(planes))
    return harness.reader(harness.ROOT, metric).read(rec)


def calls(starts, *, dispatch=100, select=30, invoke=60, busy=(110, 400)):
    """Host spans and device ops of closed-loop calls starting at
    ``starts``: each dispatch opens with its selection and ends with its
    invoke; the device works over [s + busy[0], s + busy[1])."""
    host, ops = [], []
    for s in starts:
        host += [(s, s + dispatch, "dispatch:spmv_dia"),
                 (s + 1, s + 1 + select, "dispatch.select:spmv_dia"),
                 (s + dispatch - invoke, s + dispatch,
                  "dispatch.invoke:spmv_dia")]
        ops.append((s + busy[0], s + busy[1], "spmv_dia.1"))
    return host, ops


def test_readers_on_synthetic_calls():
    host, ops = calls([0, 500, 1000, 1500])
    tr = trace.Trace(device_ops={"/device:TPU:0": ops},
                     host=host + [(0, 2000, "bench.window")],
                     window=(0, 2000))
    assert read("select_us", tr) == pytest.approx(0.030)
    assert read("invoke_us", tr) == pytest.approx(0.060)
    # idle (2000 - 4 * 290) / 4 = 210 ns a call, less 100 ns of dispatch
    assert read("runtime_gap_us", tr) == pytest.approx(0.110)


def test_only_spans_of_that_name_inside_the_window_count():
    host, ops = calls([0, 500])
    host += [(600, 900, "dispatch.select:spmv_ell"),
             (700, 800, "dispatch.select:spmv_dia_x"),
             (-50, -10, "dispatch.select:spmv_dia"),
             (950, 1100, "dispatch.select:spmv_dia")]
    tr = trace.Trace(device_ops={"/device:TPU:0": ops}, host=host,
                     window=(0, 1000))
    assert spans.durations_ns(tr, "dispatch.select:spmv_dia") == [30, 30]
    assert read("select_us", tr) == pytest.approx(0.030)


@pytest.mark.parametrize("metric", METRICS)
def test_no_spans_reads_none(metric):
    _, ops = calls([0, 500])
    tr = trace.Trace(device_ops={"/device:TPU:0": ops},
                     host=[(0, 1000, "bench.window"), (10, 20, "bench.call")],
                     window=(0, 1000))
    assert read(metric, tr) is None
    assert read(metric, None) is None


def test_runtime_gap_averages_device_busy_over_planes():
    host, ops0 = calls([0, 500], busy=(110, 400))
    _, ops1 = calls([0, 500], busy=(110, 300))
    tr = trace.Trace(device_ops={"/device:TPU:0": ops0,
                                 "/device:TPU:1": ops1},
                     host=host, window=(0, 1000))
    both = ("/device:TPU:0", "/device:TPU:1")
    # busy 580 and 380, mean 480: idle 520 / 2 calls - 100 ns of dispatch
    assert read("runtime_gap_us", tr, both) == pytest.approx(0.160)
    assert read("runtime_gap_us", tr, both[:1]) == pytest.approx(0.110)
    assert read("runtime_gap_us", tr, ()) is None


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(WITH_SPANS):
        pytest.fail(f"recorded trace missing: {WITH_SPANS}")
    return trace.load(WITH_SPANS)


def test_recorded_trace_is_small():
    assert os.path.getsize(WITH_SPANS) < 100_000


def test_recorded_spans_nest_once_per_call(recorded):
    lo, hi = recorded.window
    named = {n: sorted((s, e) for s, e, name in recorded.host
                       if name == n and lo <= s and e <= hi)
             for n in ("dispatch:spmv_dia", "dispatch.select:spmv_dia",
                       "dispatch.invoke:spmv_dia")}
    outer = named["dispatch:spmv_dia"]
    assert len(outer) >= 3
    assert len(named["dispatch.select:spmv_dia"]) == len(outer)
    assert len(named["dispatch.invoke:spmv_dia"]) == len(outer)
    for (s, e), (ss, se), (is_, ie) in zip(
            outer, named["dispatch.select:spmv_dia"],
            named["dispatch.invoke:spmv_dia"]):
        assert s <= ss <= se <= is_ <= ie <= e
    # one kernel launch per dispatch
    kernel = [o for o in trace.clip(recorded.device_ops["/device:TPU:0"],
                                    lo, hi) if "spmv_dia" in o[2]]
    assert abs(len(kernel) - len(outer)) <= 1


def test_recorded_trace_reads_every_metric(recorded):
    values = {m: read(m, recorded) for m in METRICS}
    # six calls, the Python tracer on (the benchmark's traced window)
    assert values == pytest.approx({"select_us": 189.893333,
                                    "invoke_us": 304.451833,
                                    "runtime_gap_us": 610.377167})
    outer = spans.mean_us(recorded, "dispatch:spmv_dia")
    assert values["select_us"] + values["invoke_us"] <= outer


@pytest.mark.parametrize("metric", METRICS)
def test_trace_without_program_spans_reads_none(metric):
    assert read(metric, trace.load(WITHOUT_SPANS)) is None
