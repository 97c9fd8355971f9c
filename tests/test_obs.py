"""The observability plane (DESIGN.md §14): span tracer + Chrome-trace
export, the dispatch explain API, serve/dispatch metrics, and cost-model
drift detection.

Contracts under test: span nesting and the Chrome trace-event schema
round-trip through ``save``; ``explain`` returns the same winner
``select``/``dispatch`` uses, with a rejection reason on every loser
(asserted on flash_attention under an O4 mesh, where the table spans
chip kernels, the block-sparse gate, and the mesh-scoped ring); the
log2 histogram bucketing; the drift detector flagging an injected stale
calibration, both directly and through an instrumented dispatch; and
the disabled tracer being a no-op (nothing recorded, negligible cost).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ExecLevel, bind, costmodel, registry, use_level
from repro.numerics import sparse
from repro.obs import drift, explain, explain_str, metrics, trace


@pytest.fixture(autouse=True)
def _fresh_obs():
    """The tracer, drift detector, and dispatch metrics are process
    globals; every test starts (and leaves) them clean."""
    trace.TRACER.disable()
    trace.TRACER.clear()
    drift.DETECTOR.clear()
    metrics.METRICS.reset("t.")
    yield
    trace.TRACER.disable()
    trace.TRACER.clear()
    drift.DETECTOR.clear()
    metrics.METRICS.reset("t.")


@pytest.fixture
def _no_ambient_plane(monkeypatch):
    """./test.sh runs with REPRO_KERNELS=interpret — an explicit plane
    request that reorders selection; these tests assert the unrequested
    ranking."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)


def _mm_args():
    a = jnp.ones((16, 16), jnp.float32)
    return a, a


def _fa_args():
    # L=32 divides 2 * ring(8) = 16?  32 % 16 == 0 — the zig-zag causal
    # ring is admissible on the mesh8 fixture's data axis
    q = jnp.ones((1, 4, 32, 8), jnp.float32)
    k = jnp.ones((1, 2, 32, 8), jnp.float32)
    return q, k, k


# ---------------------------------------------------------------------------
# tracer: nesting, export schema, ring bound, disabled no-op
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_nesting_and_chrome_export(self, tmp_path):
        tr = trace.Tracer()
        tr.enable()
        with tr.span("outer", cat="t", a=1):
            with tr.span("inner", cat="t"):
                pass
            tr.event("mark", cat="t", n=2)
        doc = tr.chrome_trace()
        evs = doc["traceEvents"]
        # spans emit on exit: inner completes first
        assert [e["name"] for e in evs] == ["inner", "mark", "outer"]
        inner, mark, outer = evs
        assert inner["ph"] == "X" and outer["ph"] == "X"
        assert mark["ph"] == "i" and mark["s"] == "t"
        assert inner["args"]["parent"] == "outer"
        assert outer["args"]["a"] == 1
        # the child lies within the parent's bounds (ts/dur microseconds)
        assert outer["ts"] <= inner["ts"]
        assert (inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-6)

        path = tmp_path / "trace.json"
        tr.save(str(path))
        loaded = json.loads(path.read_text())
        assert len(loaded["traceEvents"]) == 3
        for ev in loaded["traceEvents"]:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        assert loaded["otherData"]["dropped_events"] == 0

    def test_ring_buffer_keeps_most_recent(self):
        tr = trace.Tracer(capacity=4)
        tr.enable()
        for i in range(10):
            tr.event(f"e{i}")
        assert len(tr) == 4
        assert tr.dropped == 6
        assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]

    def test_disabled_tracer_records_nothing(self):
        tr = trace.TRACER
        assert not tr.enabled
        with tr.span("x", cat="y", attr=1):
            tr.event("z")
        assert len(tr) == 0

    def test_disabled_span_overhead_smoke(self):
        """200k disabled spans complete in wall time that would be
        impossible if the off path allocated or locked — a smoke bound,
        not a benchmark (CI machines vary wildly)."""
        tr = trace.TRACER
        t0 = trace.clock()
        for _ in range(200_000):
            with tr.span("hot"):
                pass
        assert trace.clock() - t0 < 5.0

    def test_scoped_tracing_restores_state(self):
        with trace.TRACER.tracing():
            assert trace.TRACER.enabled
            trace.TRACER.event("inside")
        assert not trace.TRACER.enabled
        assert len(trace.TRACER) == 1


# ---------------------------------------------------------------------------
# explain: same winner as dispatch, a reason on every loser
# ---------------------------------------------------------------------------

class TestExplain:
    def test_explain_agrees_with_dispatch_under_mesh(
            self, mesh8, _no_ambient_plane):
        """The acceptance table: flash_attention under use_level(O4) on
        the 8-device mesh lists ring, the dense kernels, and the
        block-sparse candidates; the selected row is the variant
        select()/dispatch() runs, and every loser carries its reason."""
        q, k, v = _fa_args()
        with use_level(ExecLevel.O4, mesh8):
            rows = explain("flash_attention", q, k, v)
            sel = registry.select("flash_attention", q, k, v)

        assert [r["rank"] for r in rows] == list(range(len(rows)))
        winners = [r for r in rows if r["selected"]]
        assert len(winners) == 1
        assert winners[0]["variant"] == sel.name == "ring"
        assert winners[0]["reason"].startswith("selected")
        assert winners[0]["ambient_scope"] == "mesh"
        assert winners[0]["level"] == "O4"

        by_name = {r["variant"]: r for r in rows}
        # the table spans all three families the issue names
        assert {"ring", "pallas", "xla", "blocksparse"} <= set(by_name)
        # every loser has a reason from the documented vocabulary
        prefixes = ("plane-unavailable", "scope-mismatch",
                    "available-predicate", "accepts-predicate",
                    "outranked")
        for r in rows:
            if not r["selected"]:
                assert r["reason"].startswith(prefixes), r
        # CPU has no Mosaic: the pallas-plane kernels are rejected on
        # plane, the dense-mask gate rejects blocksparse_interpret
        assert by_name["pallas"]["reason"].startswith("plane-unavailable")
        assert by_name["blocksparse"]["reason"].startswith(
            "plane-unavailable")
        assert by_name["blocksparse_interpret"]["reason"].startswith(
            "accepts-predicate")
        # L=32 < the chunked threshold; the oracle is merely outranked
        assert by_name["xla_chunked"]["reason"].startswith(
            "accepts-predicate")
        assert by_name["xla"]["reason"].startswith("outranked")

        # the renderer accepts the table
        assert "ring" in explain_str(rows)

    def test_explain_agrees_on_chip(self, _no_ambient_plane):
        q, k, v = _fa_args()
        rows = explain("flash_attention", q, k, v)
        sel = registry.select("flash_attention", q, k, v)
        winners = [r for r in rows if r["selected"]]
        assert len(winners) == 1 and winners[0]["variant"] == sel.name
        # mesh-scoped ring is inadmissible without an ambient mesh
        ring = next(r for r in rows if r["variant"] == "ring")
        assert ring["reason"].startswith("scope-mismatch")

    def test_explain_smoke_matmul_and_spmv(self):
        """The tier-1 smoke the CI workflow leans on: a non-empty ranked
        table with exactly one winner for matmul and solver_spmv."""
        a, b = _mm_args()
        rows = explain("matmul", a, b)
        assert rows and sum(r["selected"] for r in rows) == 1
        assert all(r.get("reason") for r in rows)

        csr = sparse.csr_from_dense(sparse.banded_spd(64, 3, seed=1))
        x = bind(np.ones((64,), np.float32))
        rows = explain("solver_spmv", csr, x)
        assert rows and sum(r["selected"] for r in rows) == 1
        assert all(r.get("reason") for r in rows)

    def test_explain_pinned_variant(self):
        a, b = _mm_args()
        rows = explain("matmul", a, b, variant="xla")
        assert len(rows) == 1
        assert rows[0]["selected"] and rows[0]["source"] == "pinned"

    def test_explain_reports_calibration(self, _no_ambient_plane):
        """With injected measured seconds the winner flips and the table
        says why — the §11 precedence made visible."""
        csr = sparse.csr_from_dense(sparse.banded_spd(64, 3, seed=1))
        x = bind(np.ones((64,), np.float32))
        m = costmodel.get_model()
        m.record("solver_spmv", "spmv1", seconds=1e-4, args=(csr, x))
        m.record("solver_spmv", "spmv2", seconds=5e-4, args=(csr, x))
        rows = explain("solver_spmv", csr, x)
        winner = next(r for r in rows if r["selected"])
        assert winner["variant"] == "spmv1"
        assert winner["source"] == "calibrated"
        assert winner["calibrated_seconds"] == pytest.approx(1e-4)
        assert registry.select("solver_spmv", csr, x).name == "spmv1"
        loser = next(r for r in rows if r["variant"] == "spmv2")
        assert loser["reason"].startswith("outranked")

    def test_dispatch_emits_span_and_counters(self):
        a, b = _mm_args()
        before = sum(v["value"] for k, v in
                     metrics.METRICS.snapshot("dispatch.matmul.").items())
        with trace.TRACER.tracing():
            registry.dispatch("matmul", a, b)
        evs = trace.TRACER.events()
        span = next(e for e in evs if e["name"] == "dispatch:matmul")
        assert span["ph"] == "X"
        assert {"op", "variant", "plane", "scope", "level",
                "mesh"} <= set(span["args"])
        after = sum(v["value"] for k, v in
                    metrics.METRICS.snapshot("dispatch.matmul.").items())
        assert after == before + 1


# ---------------------------------------------------------------------------
# the profiler bridge: registry dispatch spans on the profiler's timeline
# ---------------------------------------------------------------------------

DISPATCH_SPANS = ("dispatch:spmv_dia", "dispatch.select:spmv_dia",
                  "dispatch.invoke:spmv_dia")


def _dia_args():
    offsets = (-1, 0, 1)
    diags = jnp.ones((len(offsets), 32), jnp.float32)
    return diags, offsets, jnp.arange(32, dtype=jnp.float32)


class TestProfilerBridge:
    def test_off_span_is_the_shared_noop(self):
        assert not trace.TRACER.recording()
        assert trace.TRACER.span("x", cat="y", a=1) is trace._NULL_SPAN

    def test_untraced_dispatch_takes_the_fast_path(self, monkeypatch):
        def traced(*_):
            raise AssertionError("traced dispatch with nothing recording")

        monkeypatch.setattr(registry.REGISTRY, "_dispatch_traced", traced)
        diags, offsets, x = _dia_args()
        jax.block_until_ready(
            registry.dispatch("spmv_dia", diags, offsets, x))

    def test_ring_dispatch_span_encloses_select_then_invoke(self):
        diags, offsets, x = _dia_args()
        with trace.TRACER.tracing():
            registry.dispatch("spmv_dia", diags, offsets, x)
        evs = {e["name"]: e for e in trace.TRACER.events()}
        outer, select, invoke = (evs[n] for n in DISPATCH_SPANS)
        end = outer["ts"] + outer["dur"] + 1e-6
        for inner in (select, invoke):
            assert inner["args"]["parent"] == "dispatch:spmv_dia"
            assert outer["ts"] <= inner["ts"]
            assert inner["ts"] + inner["dur"] <= end
        assert select["ts"] + select["dur"] <= invoke["ts"] + 1e-6
        assert outer["args"]["variant"] == invoke["args"]["variant"]
        assert {"op", "variant", "plane", "scope", "level",
                "mesh"} <= set(outer["args"])

    def test_profiler_session_records_the_dispatch_spans(self, tmp_path):
        """A profiler session alone switches the spans on: they land in
        the ``.xplane.pb`` under their own names, nested, and the ring
        stays empty."""
        from bench import trace as bench_trace

        diags, offsets, x = _dia_args()
        jax.block_until_ready(
            registry.dispatch("spmv_dia", diags, offsets, x))   # warm
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(bench_trace.WINDOW_SPAN):
                assert trace.TRACER.recording()
                jax.block_until_ready(
                    registry.dispatch("spmv_dia", diags, offsets, x))
                trace.TRACER.event("t.mark")
        finally:
            jax.profiler.stop_trace()
        assert not trace.TRACER.recording()
        assert len(trace.TRACER) == 0
        tr = bench_trace.load(str(tmp_path))
        lo, hi = tr.window
        found = {}
        for s, e, name in tr.host:
            if lo <= s and e <= hi:
                found.setdefault(name, []).append((s, e))
        assert "t.mark" in found
        for name in DISPATCH_SPANS:
            assert len(found.get(name, [])) == 1, (name, sorted(found))
        (s, e), = found["dispatch:spmv_dia"]
        (ss, se), = found["dispatch.select:spmv_dia"]
        (is_, ie), = found["dispatch.invoke:spmv_dia"]
        assert s <= ss <= se <= is_ <= ie <= e


# ---------------------------------------------------------------------------
# metrics: instruments, log2 buckets, registry semantics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_histogram_log2_buckets(self):
        h = metrics.Histogram()
        for v in (0.75, 0.75, 1.0, 3.0, 0.0):
            h.record(v)
        # (0.5, 1] is bucket 0 — 0.75 twice and the exact power 1.0;
        # 3.0 lands in (2, 4] = bucket 2; 0.0 in the zero count
        assert h.buckets == {0: 3, 2: 1}
        assert h.zero == 1
        assert h.count == 5
        assert h.mean == pytest.approx((0.75 * 2 + 1.0 + 3.0) / 5)
        assert h.quantile(0.5) == 1.0       # bucket upper bound
        snap = h.snapshot()
        assert snap["buckets"] == {"0": 3, "2": 1}
        assert snap["min"] == 0.0 and snap["max"] == 3.0

    def test_weighted_record(self):
        h = metrics.Histogram()
        h.record(0.002, n=5)                # one iteration, five tokens
        assert h.count == 5
        assert h.total == pytest.approx(0.01)

    def test_registry_kinds_and_reset(self):
        m = metrics.METRICS
        m.counter("t.c").inc(2.5)
        m.gauge("t.g").set(7)
        m.histogram("t.h").record(0.3)
        with pytest.raises(TypeError):
            m.gauge("t.c")                  # kind mismatch is loud
        snap = m.snapshot("t.")
        assert snap["t.c"] == {"type": "counter", "value": 2.5}
        assert snap["t.g"]["value"] == 7.0
        assert snap["t.h"]["count"] == 1
        m.reset("t.")
        assert m.snapshot("t.") == {}


# ---------------------------------------------------------------------------
# drift: stale calibration flags, dispatch integration
# ---------------------------------------------------------------------------

class TestDrift:
    def test_injected_stale_entry_flags(self):
        a, b = _mm_args()
        m = costmodel.get_model()
        m.record("matmul", "xla", seconds=1e-6, args=(a, b))
        m.record("matmul", "interpret", seconds=1e-3, args=(a, b))

        d = drift.DETECTOR
        d.observe("matmul", "xla", 1.0, (a, b), {})          # 1e6x off
        d.observe("matmul", "interpret", 1.2e-3, (a, b), {})  # holds
        rows = d.report()
        by_variant = {r["variant"]: r for r in rows}
        assert by_variant["xla"]["stale"]
        assert by_variant["xla"]["ratio"] > drift.threshold()
        assert not by_variant["interpret"]["stale"]
        assert rows[0]["variant"] == "xla"   # worst first
        assert d.flagged() == [by_variant["xla"]]

    def test_unmatched_observations_counted(self):
        d = drift.DETECTOR
        d.observe("matmul", "xla", 1e-3, _mm_args(), {})
        assert d.unmatched == 1              # isolated model: no entry
        assert d.report() == []

    def test_collect_scopes_collection(self):
        assert not drift.collecting()
        with drift.collect():
            assert drift.collecting()
            with drift.collect():
                assert drift.collecting()
        assert not drift.collecting()

    def test_dispatch_under_collect_flags_stale_model(self):
        """End-to-end: a stale stored calibration for whatever variant
        dispatch picks is flagged after one instrumented call."""
        a, b = _mm_args()
        v = registry.select("matmul", a, b)
        # a singleton record never re-ranks selection (§11), but drift
        # still compares against it — inject an absurdly fast stored time
        costmodel.get_model().record("matmul", v.name, seconds=1e-12,
                                     args=(a, b))
        with drift.collect():
            registry.dispatch("matmul", a, b)
        flagged = drift.DETECTOR.flagged()
        assert flagged and flagged[0]["op"] == "matmul"
        assert flagged[0]["variant"] == v.name
        assert flagged[0]["ratio"] > drift.threshold()

    def test_dispatch_without_collect_records_nothing(self):
        a, b = _mm_args()
        registry.dispatch("matmul", a, b)
        assert drift.DETECTOR.report() == []
        assert drift.DETECTOR.unmatched == 0


# ---------------------------------------------------------------------------
# serve loop integration: phase spans, metrics, heartbeat
# ---------------------------------------------------------------------------

class TestServeObservability:
    def test_serve_loop_spans_metrics_heartbeat(self):
        from repro.configs.base import ModelConfig
        from repro.models.lm import LM
        from repro.serve import ContinuousEngine, SamplingParams

        cfg = ModelConfig(name="obstest", family="dense", num_layers=2,
                          d_model=32, vocab_size=64, num_heads=4,
                          num_kv_heads=2, head_dim=8, d_ff=64,
                          dtype="float32", param_dtype="float32",
                          remat=False)
        lm = LM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        eng = ContinuousEngine(lm, params, num_slots=2, max_len=64,
                               chunk_size=4,
                               sampling=SamplingParams(greedy=True))
        reqs = [(np.arange(8) % 64, 3), (np.arange(5) % 64, 2)]
        metrics.METRICS.reset("serve.")
        with trace.TRACER.tracing():
            outs = eng.serve(reqs)
        assert [len(o) for o in outs] == [3, 2]

        names = {e["name"] for e in trace.TRACER.events()}
        assert {"serve.admit", "serve.prefill_chunk", "serve.decode",
                "serve.demux"} <= names

        snap = metrics.METRICS.snapshot("serve.")
        assert snap["serve.submitted"]["value"] == 2
        assert snap["serve.admitted"]["value"] == 2
        assert snap["serve.recycled"]["value"] == 2
        assert snap["serve.tokens"]["value"] == 5
        assert snap["serve.ttft_s"]["count"] == 2
        assert snap["serve.token_latency_s"]["count"] == 5
        assert snap["serve.occupancy_dist"]["count"] > 0
        assert 0 < snap["serve.occupancy_dist"]["max"] <= 1.0

        beats = eng.heartbeats.all()
        assert 0 in beats
        assert beats[0].step > 0
        assert beats[0].occupancy is not None

    def test_heartbeat_occupancy_file_round_trip(self, tmp_path):
        from repro.runtime.fault_tolerance import FileHeartbeatStore

        store = FileHeartbeatStore(str(tmp_path / "hb"))
        store.post(3, 17, occupancy=0.625)
        store.post(4, 17)                   # occupancy stays optional
        beats = store.all()
        assert beats[3].occupancy == pytest.approx(0.625)
        assert beats[4].occupancy is None
