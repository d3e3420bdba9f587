"""BENCHMARK.json against the contract's letter, so that a PR that adds an
entry learns here and not from the driver that its file is refused."""

import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names, files = set(), set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        names.add(c["name"])
        files.add(c["file"])
    assert len(names) == len(bench["configs"])
    assert names == {w["config"] for w in bench["workloads"]}


def test_workloads(bench):
    assert 1 <= len(bench["workloads"]) <= 24
    seen = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_metrics(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert len(e2e) == len(bench["end_to_end"])

    def cells_of(m):
        assert set(m.get("workloads", cells)) <= set(cells)
        return set(m.get("workloads", cells))

    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        # PR 35's issue asked to widen the upper limit to what the serving
        # sets ask; the contract admits no bound over 0.1 and refuses the
        # file, so a metric whose sets ask for more keeps 0.1 and PERF.md
        # says what that bound cannot tell (section 2)
        assert 0.01 <= m["bound"] <= 0.1
    assert "workloads" not in e2e["setup_s"]
    layers = set()
    assert 1 <= len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"]) and m["name"] not in e2e
        # what a per-layer metric moves is reported in every cell it is in
        assert cells_of(m) <= cells_of(e2e[m["moves"]])
        layers.add(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for cell in cells:
        mine = [m["name"] for m in bench["end_to_end"] if cell in cells_of(m)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in cells_of(m) for m in bench["per_layer"])
    # PERF.md's list of layers has each layer's name, letter for letter
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def test_retired_metrics_are_gone_entry_and_reader(bench):
    # prefill_tick_share read prompt tokens per busy slot-tick since PR 29
    # (128.78 "%"): a percent over 105 invites the driver's refusal
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for retired in ("prefill_tick_share",):
        assert retired not in names
        assert not os.path.exists(os.path.join(HERE, "metrics", retired + ".py"))
    stolen = next(m for m in bench["per_layer"]
                  if m["name"] == "window_stolen_ms")
    assert stolen == {"name": "window_stolen_ms", "unit": "ms",
                      "better": "lower", "source": "host_clock",
                      "layer": "load generator", "moves": "ttft_p90_ms",
                      "workloads": ["lm-big_serve_chat"]}


def test_setup_s_leaves_out_the_runtimes_start(monkeypatch):
    import time
    import types
    from benchmark import harness

    def slow_device(chips):
        time.sleep(0.05)
        return {"count": chips}
    monkeypatch.setattr(harness, "device_facts", slow_device)
    cell = types.SimpleNamespace(chips=4)
    args = types.SimpleNamespace(seed=7, seconds=1.0)
    t0 = time.perf_counter() - 3.0          # three seconds of imports
    run = harness.start_run(cell, args, t0)
    assert run.device == {"count": 4} and run.seed == 7
    assert set(run.setup_parts) == {"import", "runtime_start"}
    assert run.setup_parts["import"] == pytest.approx(3.0, abs=0.05)
    assert 0.05 <= run.setup_parts["runtime_start"] < 0.5
    run.setup_parts.update(runtime_start=12.5, build=0.5)
    run.open_window(t_open=t0 + 30.0)
    assert run.setup_s == pytest.approx(17.5)


def test_every_file_under_paths_is_named_from_a_names_letters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel), rel
