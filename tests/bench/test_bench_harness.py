"""The harness finds configurations, traffic mixes, cells, entries and
per-layer metrics by name, and BENCHMARK.json keeps to its contract."""
import json
import os
import re
import textwrap

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, cells // 2)
    # a full check with 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        assert _line(c["source"]) and _line(c["why"])
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(c["reduced"])
        names.add(c["name"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == names
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m["workloads"]) <= {w["name"] for w in bench["workloads"]}
        assert callable(harness.reader(harness.ROOT, m["name"]).read)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert _line(w["why"])
        s = harness.spec(w["name"])
        assert s.per_layer, f"{w['name']} reports no per-layer metric"
        assert len(s.end_to_end) >= 2, f"{w['name']}: setup_s alone"
        moved = {m["name"] for m in s.end_to_end}
        assert all(m["moves"] in moved for m in s.per_layer)
        assert set(s.cell["limits"])
    assert len(json.dumps(bench)) <= 64 * 1024


def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))


def test_new_config_mix_cell_and_metric_are_found_by_name(tmp_path,
                                                           monkeypatch):
    """A cell made only of new files runs without an edit to the harness."""
    root = str(tmp_path)
    _write(root, "BENCHMARK.json", json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "toy", "source": "none", "reduced": [],
                     "file": "bench/configs/toy.json", "why": "test"}],
        "workloads": [{"name": "toy.count", "config": "toy",
                       "traffic": "count", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "call_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "toy_calls", "unit": "count",
                       "better": "higher", "source": "host_clock",
                       "layer": "toy", "moves": "call_ms"}]}))
    _write(root, "bench/configs/toy.json", '{"size": 8, "reduced": []}')
    _write(root, "bench/traffic/count.json", '{"entry": "toy_sum"}')
    _write(root, "bench/cells/toy.count.json", '{"limits": {"err": 0}}')
    _write(root, "bench/entries/toy_sum.py", """
        import jax.numpy as jnp
        from bench.harness import Work

        class Cell:
            def __init__(self, config, seed, n):
                self.x = jnp.arange(config["size"]) + seed
            def call(self, i):
                return jnp.sum(self.x) + i
            def warm(self):
                self.call(0).block_until_ready()
            def variants(self):
                return {}
            stat_of = staticmethod(lambda out: None)
            answer = staticmethod(lambda out: out)
            def stats(self, outs):
                return {}
            def work(self, stats):
                return Work(flops=8, hbm_bytes=32)
            def free_program(self):
                pass
            def check(self, kept):
                return {"err": [abs(float(y) - float(jnp.sum(self.x)) - i)
                                for i, y in kept]}

        def build(config, traffic, seed, devices, **kw):
            return Cell(config, seed, len(devices))
        """)
    _write(root, "bench/metrics/toy_calls.py", """
        def read(rec):
            return float(rec.calls)
        """)
    monkeypatch.setattr(harness, "_enable_compile_cache", lambda root: None)
    out = harness.run("toy.count", 3, 0.05, False, root=root,
                      require_accelerator=False, log=lambda s: None)
    assert out["correct"] and out["attempted"] >= 1
    assert set(out["metrics"]) == {"call_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    traced = harness.run("toy.count", 3, 0.05, True, root=root,
                         require_accelerator=False, log=lambda s: None)
    assert traced["metrics"]["toy_calls"]["value"] >= 1
    assert {"busy_s", "window_s"} <= set(traced["device"])


def test_no_accelerator_is_refused(monkeypatch):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("an accelerator is attached")
    with pytest.raises(harness.NoAccelerator):
        harness.devices_for(1)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.spec("no.such.cell")
