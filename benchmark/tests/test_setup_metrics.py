"""The nine `setup_*` readers, each on a hand-built run and a hand-built list
of the program's kept `compile` spans (`tracing.compile_spans()`), against the
value computed by hand: the sums, the cut at the window's opening, the union
that counts overlapping spans once, and None where the program keeps no such
spans (the parent's tree, or PTPU_TRACE=0)."""

import json
import os
import threading

import pytest

from benchmark import harness
from paddle_tpu.observability import tracing
from paddle_tpu.observability.tracing import Span

MAIN = threading.main_thread().ident
T0 = 100.0          # the process's start, on perf_counter
RUNTIME_START = 8.0     # the first touch of the device: 103.5 to 111.5
T_OPEN = 130.0      # the window opens: set-up is 30 s less those 8
READERS = ("setup_import_s", "setup_trace_s", "setup_lower_s",
           "setup_compile_s", "setup_cache_load_s", "setup_programs",
           "setup_jit_calls", "setup_kernel_bodies_traced", "setup_unnamed_s")


def kept(name, start, end, thread_id=MAIN, **attrs):
    return Span("compile", name, T0 + start, T0 + end, thread_id, "", 0,
                attrs, 0, 0)


def first_run(program, start, end, trace, nested, jits, lower, compile_s,
              load, bodies, **kw):
    return kept("executor/compile_or_load", start, end, program=program,
                trace_s=trace, nested_trace_s=nested, jits=jits,
                lower_s=lower, compile_s=compile_s, cache_load_s=load,
                executables=1, cache_hit=int(load > 0), kernel_calls=4 * bodies,
                kernel_bodies_traced=bodies, **kw)


SPANS = [
    kept("paddle_tpu/import", 0.3, 3.4),
    kept("executor/build_step", 11.6, 11.7, program_version=3, n_fetches=0),
    first_run("startup", 11.7, 14.0, 0.2, 0.1, 40, 1.9, 0.01, 0.1, 0),
    # a weight builder's jitted helpers: sums, and no interval of compiling
    kept("jax/unscoped", 11.0, 19.5, program="unscoped", trace_s=0.5,
         nested_trace_s=0.0, jits=12, lower_s=0.25, compile_s=0.0,
         cache_load_s=0.125, executables=12, cache_hit=1),
    kept("executor/build_step", 20.0, 20.5, program_version=9, n_fetches=1),
    first_run("train_step", 20.5, 27.5, 3.5, 1.6, 3400, 1.0, 0.02, 2.2, 10),
    # a nested first run (counted once in the union) and one on a thread
    # of its own (not the main thread's time)
    first_run("helper", 21.0, 22.0, 0.25, 0.0, 3, 0.125, 0.0, 0.0625, 1),
    first_run("offload", 12.0, 16.0, 0.5, 0.0, 1, 0.5, 0.0, 0.25, 0,
              thread_id=MAIN + 1),
    # after the window opened: a recompile in the drain is not set-up
    first_run("late", 31.0, 32.0, 0.7, 0.0, 5, 0.3, 4.0, 0.0, 2),
    kept("jax/unscoped", 40.0, 90.0, program="unscoped", trace_s=9.0,
         nested_trace_s=0.0, jits=99, lower_s=9.0, compile_s=9.0,
         cache_load_s=9.0, executables=9, cache_hit=0),
]

BY_HAND = {
    "setup_import_s": 3.1,
    "setup_trace_s": 0.2 + 0.5 + 3.5 + 0.25 + 0.5,
    "setup_lower_s": 1.9 + 0.25 + 1.0 + 0.125 + 0.5,
    "setup_compile_s": 0.01 + 0.02,
    "setup_cache_load_s": 0.1 + 0.125 + 2.2 + 0.0625 + 0.25,
    "setup_programs": 4,
    "setup_jit_calls": 40 + 12 + 3400 + 3 + 1,
    "setup_kernel_bodies_traced": 0 + 10 + 1 + 0,
    # 22 s of set-up less import 3.1, build 0.1 + 0.5, startup 2.3 and the
    # step's 7.0 (the helper's second inside it once, the other thread's
    # four and the unscoped record's interval not at all)
    "setup_unnamed_s": 22.0 - (3.1 + 0.1 + 2.3 + 0.5 + 7.0),
}


def run_of():
    run = harness.Run(None, 0, 1.0, {})
    run.t0 = T0
    run.setup_parts = {"import": 3.5, "runtime_start": RUNTIME_START}
    run.open_window(T_OPEN)
    return run


def read(metric, spans, monkeypatch, run=None):
    monkeypatch.setattr(tracing, "compile_spans", lambda: list(spans),
                        raising=False)
    return harness.load_module("metrics", metric).read(run or run_of())


def test_the_hand_built_run():
    assert run_of().setup_s == pytest.approx(22.0)
    assert set(READERS) == set(BY_HAND)


@pytest.mark.parametrize("metric", READERS)
def test_reader_gives_the_value_computed_by_hand(metric, monkeypatch):
    assert read(metric, SPANS, monkeypatch) == pytest.approx(BY_HAND[metric],
                                                            abs=1e-9)


@pytest.mark.parametrize("metric", READERS)
def test_reader_gives_none_without_kept_spans(metric, monkeypatch):
    # PTPU_TRACE=0: the list is empty
    assert read(metric, [], monkeypatch) is None
    # everything the program kept ended after the window opened
    assert read(metric, SPANS[-2:], monkeypatch) is None
    # the parent's program has no such list
    monkeypatch.delattr(tracing, "compile_spans")
    assert harness.load_module("metrics", metric).read(run_of()) is None


def test_the_cut_is_the_window_s_opening(monkeypatch):
    # a first run that ends as the window opens is set-up's; a moment later
    # it is the window's
    edge = [first_run("tick", 29.0, 30.0, 0.5, 0.0, 1, 0.25, 0.0, 0.125, 0)]
    assert read("setup_programs", edge, monkeypatch) == 1
    late = [first_run("tick", 29.0, 30.001, 0.5, 0.0, 1, 0.25, 0.0, 0.125, 0)]
    assert read("setup_programs", late, monkeypatch) is None


def test_a_reader_leaves_out_what_no_span_carries(monkeypatch):
    # the import alone (a process that ran no program): seconds of import,
    # no program, and no sum of an attr nobody carries
    alone = SPANS[:1]
    assert read("setup_import_s", alone, monkeypatch) == pytest.approx(3.1)
    assert read("setup_programs", alone, monkeypatch) == 0
    assert read("setup_trace_s", alone, monkeypatch) is None
    assert read("setup_unnamed_s", alone, monkeypatch) == pytest.approx(18.9)
    # the unscoped record has no kernel counters: the sum is over the rest
    assert read("setup_kernel_bodies_traced", SPANS[3:4], monkeypatch) is None
    assert read("setup_import_s", SPANS[1:], monkeypatch) is None


def test_unnamed_never_reads_below_zero(monkeypatch):
    # spans that claim more than set-up held (a clock that was set back)
    over = [kept("paddle_tpu/import", -50.0, 29.0),
            first_run("step", 5.0, 29.9, 1.0, 0.0, 1, 1.0, 0.0, 1.0, 0)]
    assert read("setup_unnamed_s", over, monkeypatch) == 0.0
    # clipped to [process start, the window's opening]: what is left is the
    # last tenth of a second... less the runtime's start, which set-up
    # leaves out: below zero, so zero
    whole = [kept("paddle_tpu/import", 0.0, 29.9)]
    assert read("setup_unnamed_s", whole, monkeypatch) == 0.0
    part = [kept("paddle_tpu/import", 0.0, 20.0)]
    assert read("setup_unnamed_s", part, monkeypatch) == pytest.approx(2.0)


def test_every_reader_is_in_the_manifest_as_it_states_itself():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-9:]] == list(READERS)
    for name in READERS:
        reader, m = harness.load_module("metrics", name), entries[name]
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            m["unit"], m["source"], m["layer"], m["moves"])
        assert (m["layer"], m["moves"], m["better"]) == ("set-up", "setup_s",
                                                         "lower")
        assert m["workloads"] == cells


def test_readers_on_the_program_s_own_spans():
    """Not hand-built: a tiny program's first runs, read through the same
    readers (the spans' clock is the run's)."""
    import time
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    pt.reset_default_programs()
    pt.reset_global_scope()
    tracing.clear()
    run = harness.Run(None, 0, 1.0, {})
    run.t0 = time.perf_counter()
    run.setup_parts = {"runtime_start": 0.0}
    with pt.core.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, size=2))
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[loss])
    tracing.mark()
    run.open_window(time.perf_counter())
    exe.run(feed={"x": np.ones((3, 4), "float32")}, fetch_list=[loss])
    got = {m: harness.load_module("metrics", m).read(run) for m in READERS}
    assert got["setup_programs"] == 2 and got["setup_import_s"] is None
    assert got["setup_trace_s"] > 0 and got["setup_lower_s"] > 0
    assert got["setup_compile_s"] > 0 and got["setup_cache_load_s"] == 0
    assert got["setup_jit_calls"] >= 2
    assert got["setup_kernel_bodies_traced"] == 0
    assert 0 <= got["setup_unnamed_s"] < run.setup_s
    named = sum(s.end - s.start for s in tracing.compile_spans()
                if s.end <= run.t0 + run.setup_s)
    assert got["setup_unnamed_s"] == pytest.approx(run.setup_s - named)
