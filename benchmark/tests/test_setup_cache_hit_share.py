"""`setup_cache_hit_share` on hand-built runs and hand-built kept `compile`
spans, like `test_setup_metrics.py` for the nine before it: 100 where every
executable of set-up was a load, the share between, None where no span
carries `cache_loads` (the parent's program) or set-up built no executable,
spans after the window's opening left out, and the manifest's entry."""

import json
import os

import pytest

from benchmark import harness
from benchmark.tests.test_setup_metrics import kept, read, run_of

NAME = "setup_cache_hit_share"


def built(program, start, end, executables, loads, **kw):
    name = ("jax/unscoped" if program == "unscoped"
            else "executor/compile_or_load")
    return kept(name, start, end, program=program, executables=executables,
                cache_loads=loads, cache_hit=int(0 < executables <= loads),
                **kw)


WARM = [
    kept("paddle_tpu/import", 0.3, 3.4),
    built("unscoped", 11.0, 19.5, 67, 67),
    built("startup", 11.7, 11.8, 1, 1),
    built("mixed_tick", 20.0, 25.0, 1, 1),
]
# after the window opened: a recompile in the drain is not set-up's
LATE = [built("late", 31.0, 32.0, 5, 0), built("unscoped", 40.0, 90.0, 9, 0)]


def test_every_executable_loaded_reads_100(monkeypatch):
    assert read(NAME, WARM, monkeypatch) == 100.0
    assert read(NAME, WARM + LATE, monkeypatch) == 100.0


@pytest.mark.parametrize("loads, share", [(0, 0.0), (16, 25.0), (61, 95.3125)])
def test_a_share_between(loads, share, monkeypatch):
    # 64 executables in all: the step's two always load
    spans = [built("unscoped", 11.0, 19.5, 62, max(loads - 2, 0)),
             built("train_step", 20.0, 27.0, 2, min(loads, 2))]
    assert read(NAME, spans, monkeypatch) == pytest.approx(share)


def test_none_without_the_attr_or_without_executables(monkeypatch):
    # the parent's spans: executables and cache_hit, no cache_loads
    parent = [kept("jax/unscoped", 11.0, 19.5, program="unscoped",
                   executables=67, cache_hit=0),
              kept("executor/compile_or_load", 20.0, 25.0, program="tick",
                   executables=1, cache_hit=1)]
    assert read(NAME, parent, monkeypatch) is None
    # nothing built during set-up: no share of nothing
    assert read(NAME, [built("startup", 11.7, 11.8, 0, 0)],
                monkeypatch) is None
    assert read(NAME, WARM[:1], monkeypatch) is None
    # PTPU_TRACE=0, and everything kept ended after the window opened
    assert read(NAME, [], monkeypatch) is None
    assert read(NAME, LATE, monkeypatch) is None


def test_none_on_a_program_without_kept_spans(monkeypatch):
    from paddle_tpu.observability import tracing
    monkeypatch.delattr(tracing, "compile_spans")
    assert harness.load_module("metrics", NAME).read(run_of()) is None


def test_the_manifest_entry():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by its name, wherever the list has it by now
    by_name = {e["name"]: e for e in bench["per_layer"]}
    reader, m = harness.load_module("metrics", NAME), by_name[NAME]
    assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
        m["unit"], m["source"], m["layer"], m["moves"]) == (
        "%", "program_span", "set-up", "setup_s")
    assert m["better"] == "higher"
    assert m["workloads"] == [w["name"] for w in bench["workloads"]]
    assert m["workloads"] == by_name["setup_compile_s"]["workloads"]
    assert sorted(m) == sorted(by_name["setup_compile_s"])


def test_on_the_program_s_own_spans():
    """Not hand-built: a first call in this CPU-pinned process, which keeps
    no cache: an executable, and no load."""
    import time
    import jax
    import numpy as np
    from paddle_tpu.observability import tracing
    tracing.clear()
    run = harness.Run(None, 0, 1.0, {})
    run.t0 = time.perf_counter()
    run.setup_parts = {"runtime_start": 0.0}
    with tracing.compile_span("executor/compile_or_load", "tiny") as sp:
        jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(np.ones(3, "f4")))
    tracing.mark()
    run.open_window(time.perf_counter())
    assert (sp.attrs["executables"], sp.attrs["cache_loads"]) == (1, 0)
    assert harness.load_module("metrics", NAME).read(run) == 0.0
