"""`tick_run_ahead_share` (PR 48) on a hand-made run against the value computed
by hand, on runs without what it reads (PTPU_TRACE=0; the parent's program,
whose ticks have no `ahead`): None; and its entry in the manifest."""

import pytest

from benchmark import harness
from test_span_metrics import read, span

# five engine steps: a mixed tick that delivers a first token (read at once), a
# decode tick launched on the idle device and held, two launched behind the
# tick before them while it ran, and the last tick before the engine idles,
# whose launch found the tick before done
SERVE = [
    span("engine/tick", 0.0, 9.0, 1, mixed=1, late=0, ahead=0),
    span("engine/tick", 10.0, 12.0, 2, mixed=0, late=1, ahead=0),
    span("engine/wait", 11.0, 12.0, 3, 2),
    span("engine/hold", 11.0, 12.0, 4, 3, early=0),
    span("engine/tick", 12.1, 15.0, 5, mixed=0, late=1, ahead=1),
    span("engine/wait", 13.0, 13.6, 6, 5),
    span("engine/device_wait", 13.0, 13.2, 7, 6),
    span("engine/copy_back", 13.2, 13.6, 8, 6),
    span("engine/wait", 13.8, 15.0, 9, 5),
    span("engine/hold", 13.8, 15.0, 10, 9, early=0),
    span("engine/tick", 15.1, 18.0, 11, mixed=0, late=1, ahead=1),
    span("engine/tick", 19.0, 22.5, 12, mixed=0, late=0, ahead=0),
    span("engine/finish", 22.6, 22.7, 13),
]


def test_reader_gives_the_value_computed_by_hand():
    assert read("tick_run_ahead_share", SERVE) == pytest.approx(40.0)
    # at most the share of ticks read late: a tick runs ahead only behind one
    assert read("tick_run_ahead_share", SERVE) <= \
        read("tick_late_read_share", SERVE)


def test_reader_gives_none_without_what_it_reads():
    assert read("tick_run_ahead_share", []) is None
    # the parent's program under this PR's benchmark files: ticks without
    # the attr
    old = [span(s.name, 1e3 * s.start, 1e3 * s.end, s.id, s.parent_id,
                **{k: v for k, v in s.attrs.items() if k != "ahead"})
           for s in SERVE]
    assert read("tick_run_ahead_share", old) is None
    assert read("tick_late_read_share", old) == pytest.approx(60.0)


def test_the_manifest_lists_it_with_the_serving_cells():
    bench = harness.load_json("..", "BENCHMARK.json")
    m, = [m for m in bench["per_layer"] if m["name"] == "tick_run_ahead_share"]
    reader = harness.load_module("metrics", "tick_run_ahead_share")
    assert m == {"name": "tick_run_ahead_share", "unit": reader.UNIT,
                 "better": "higher", "source": reader.SOURCE,
                 "layer": reader.LAYER, "moves": reader.MOVES,
                 "workloads": [w["name"] for w in bench["workloads"]
                               if "_serve_" in w["name"]]}
    # every cell it lists reports the end-to-end metric it moves
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved["workloads"])
