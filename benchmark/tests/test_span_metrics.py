"""The readers of the program's host-phase spans, each on a hand-made run of a
dozen spans against the value computed by hand, and on a run without such
spans (PTPU_TRACE=0, or a program that does not open them): None."""

import pytest

from benchmark import harness
from paddle_tpu.observability.tracing import Span


def span(name, start_ms, end_ms, id, parent_id=-1, **attrs):
    return Span("user", name, start_ms / 1e3, end_ms / 1e3, 0, "", 0, attrs,
                id, id, parent_id)


def run_of(spans):
    run = harness.Run(None, 0, 1.0, {})
    run.spans = spans
    return run


def read(metric, spans):
    return harness.load_module("metrics", metric).read(run_of(spans))


# two training steps of 100 and 120 ms on a mesh: prepare, lookup, feed,
# launch, write-back, post, fetch; 0.3 and 0.5 ms of each under no span
TRAIN = [
    span("benchmark/step", 0, 100, 1),
    span("parallel/prepare", 0.1, 2.1, 2, 1),
    span("executor/lookup", 2.1, 2.6, 3, 1),
    span("executor/feed", 2.6, 4.0, 4, 1),
    span("executor/run", 4.0, 5.0, 5, 1),
    span("executor/state_writeback", 5.0, 5.1, 6, 1),
    span("executor/post", 5.1, 5.2, 7, 1),
    span("executor/fetch", 5.3, 99.9, 8, 1),
    span("benchmark/step", 100, 220, 11),
    span("parallel/prepare", 100.2, 104.2, 12, 11),
    span("executor/lookup", 104.2, 105.2, 13, 11),
    span("executor/feed", 105.2, 106.8, 14, 11),
    span("executor/run", 106.8, 109.8, 15, 11),
    span("executor/state_writeback", 109.8, 109.9, 16, 11),
    span("executor/post", 109.9, 110.0, 17, 11),
    span("executor/fetch", 110.2, 219.9, 18, 11),
]

# two engine steps: admit, tick (dispatch (fill, launch), wait), commit, and
# in the second a finish; 0.05 and 0.12 ms of each under no span
SERVE = [
    span("benchmark/engine.step", 0, 35, 1),
    span("engine/admit", 0.02, 0.05, 2, 1),
    span("engine/tick", 0.06, 34.0, 3, 1, active=5, prefill=3, decode=2),
    span("engine/dispatch", 0.06, 1.36, 4, 3),
    span("engine/fill_feeds", 0.07, 0.37, 5, 4),
    span("engine/launch", 0.37, 1.35, 6, 4),
    span("engine/wait", 1.4, 34.0, 7, 3),
    span("engine/commit", 34.0, 34.98, 8, 1, finished=0),
    span("benchmark/engine.step", 40, 76, 11),
    span("engine/admit", 40.03, 40.06, 12, 11),
    span("engine/tick", 40.08, 74.5, 13, 11, active=4, prefill=0, decode=4),
    span("engine/dispatch", 40.08, 42.58, 14, 13),
    span("engine/fill_feeds", 40.1, 40.6, 15, 14),
    span("engine/launch", 40.6, 42.5, 16, 14),
    span("engine/wait", 42.6, 74.5, 17, 13),
    span("engine/commit", 74.5, 75.1, 18, 11, finished=1),
    span("engine/finish", 75.12, 75.95, 19, 11, n=1),
]

BY_HAND = [
    # medians of two are their means
    ("step_lookup_ms_p50", TRAIN, (0.5 + 1.0) / 2),
    ("step_launch_ms_p50", TRAIN, (1.0 + 3.0) / 2),
    ("step_prepare_ms_p50", TRAIN, (2.0 + 4.0) / 2),
    ("step_host_ms_p50", TRAIN, ((100 - 94.6) + (120 - 109.7)) / 2),
    ("step_untraced_ms_p50", TRAIN, (0.3 + 0.5) / 2),
    ("tick_fill_ms_p50", SERVE, (0.3 + 0.5) / 2),
    ("tick_launch_ms_p50", SERVE, (0.98 + 1.9) / 2),
    ("tick_commit_ms_p50", SERVE, (0.98 + (0.6 + 0.83)) / 2),
    ("tick_untraced_ms_p50", SERVE, (0.05 + 0.12) / 2),
    ("prefill_slots_per_tick", SERVE, (3 + 0) / 2),
]


@pytest.mark.parametrize("metric,spans,expected", BY_HAND,
                         ids=[m for m, _, _ in BY_HAND])
def test_reader_gives_the_value_computed_by_hand(metric, spans, expected):
    assert read(metric, spans) == pytest.approx(expected, abs=1e-9)


@pytest.mark.parametrize("metric", [m for m, _, _ in BY_HAND])
def test_reader_gives_none_without_its_spans(metric):
    assert read(metric, []) is None
    # the benchmark's own wrapper alone (PTPU_TRACE=0 records not even that;
    # a program without the span records only this)
    alone = [span("benchmark/step", 0, 100, 1),
             span("benchmark/engine.step", 100, 135, 2)]
    if "untraced" in metric:
        # nothing under the wrapper: all of it is under no program span
        assert read(metric, alone) is not None
    else:
        assert read(metric, alone) is None


def test_step_host_takes_out_the_loop_s_wait_for_an_earlier_loss():
    # the training loop keeps steps in flight and waits for an earlier one's
    # loss under its own span: the same subtraction, and a turn that only
    # filled the pipeline (no wait under it) is not counted
    piped = [span("benchmark/loss_wait" if s.name == "executor/fetch"
                  else s.name, 1e3 * s.start, 1e3 * s.end, s.id, s.parent_id)
             for s in TRAIN] + [span("benchmark/step", 220, 226, 21)]
    assert read("step_host_ms_p50", piped) == \
        pytest.approx(read("step_host_ms_p50", TRAIN), abs=1e-9)


def test_window_stolen_ms_is_wall_less_thread_time_less_waits():
    # SERVE's two engine/wait spans: 32.6 + 31.9 = 64.5 ms. A loop of 80 ms
    # whose thread ran 15.5 ms of them lost nothing; of 100 ms, 20 ms
    run = run_of(SERVE)
    run.window_clock = {"wall_s": 0.080, "thread_cpu_s": 0.0155}
    read = harness.load_module("metrics", "window_stolen_ms").read
    assert read(run) == pytest.approx(0.0, abs=1e-9)
    run.window_clock = {"wall_s": 0.100, "thread_cpu_s": 0.0155}
    assert read(run) == pytest.approx(20.0, abs=1e-9)
    # a loop that took no clock (training), or a program without the span
    run.window_clock = {}
    assert read(run) is None
    other = run_of([s for s in SERVE if s.name != "engine/wait"])
    other.window_clock = {"wall_s": 0.100, "thread_cpu_s": 0.0155}
    assert read(other) is None


class ParentSpan:
    """A span as the program recorded it before it had ids (PR 23): the
    driver lays this benchmark over that program too."""

    def __init__(self, name, start_ms, end_ms, **attrs):
        self.name, self.attrs = name, attrs
        self.start, self.end = start_ms / 1e3, end_ms / 1e3
        self.duration_ms = end_ms - start_ms


PARENT = [ParentSpan("benchmark/step", 0, 100), ParentSpan("executor/feed", 1, 2),
          ParentSpan("executor/run", 2, 4),
          ParentSpan("benchmark/engine.step", 100, 135),
          ParentSpan("engine/admit", 100, 100.1, pending=0),
          ParentSpan("engine/tick", 100.2, 134, active=3, request_ids=[]),
          ParentSpan("engine/dispatch", 100.1, 101.4, active=3)]


@pytest.mark.parametrize("metric", [m for m, _, _ in BY_HAND])
def test_reader_does_not_raise_on_the_parents_spans(metric, monkeypatch):
    from paddle_tpu.observability import tracing
    monkeypatch.delattr(tracing, "self_time_ms")    # nor had it this
    # the one span both programs open gives its number; the rest give None
    assert read(metric, PARENT) == (2.0 if metric == "step_launch_ms_p50"
                                    else None)


def test_manifest_and_readers_agree_on_the_new_metrics():
    import json
    import os
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric in [m for m, _, _ in BY_HAND] + ["window_stolen_ms"]:
        mod, entry = harness.load_module("metrics", metric), entries[metric]
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"])
