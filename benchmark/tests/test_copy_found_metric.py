"""`tick_copy_found_share` (PR 55) on a hand-made run against the value
computed by hand, on runs without what it reads (PTPU_TRACE=0; the parent's
program, whose `engine/copy_back` spans have no `found`): None; and its entry
in the manifest."""

import pytest

from benchmark import harness
from test_span_metrics import read, span

# five reads behind a wait: a sampled eager tick's (it comes the instant the
# tick is seen done: the copy is still on its way) and four of ticks read a
# launch late, one of them the instant its tick ended behind a launch that
# ran ahead
SERVE = [
    span("engine/tick", 0.0, 9.0, 1, mixed=1, late=0, ahead=0),
    span("engine/wait", 5.0, 9.0, 2, 1),
    span("engine/device_wait", 5.0, 8.6, 3, 2),
    span("engine/copy_back", 8.6, 9.0, 4, 2, found=0),
    span("engine/tick", 10.0, 12.0, 5, mixed=0, late=1, ahead=0),
    span("engine/tick", 12.1, 15.0, 6, mixed=0, late=1, ahead=1),
    span("engine/wait", 13.0, 13.4, 7, 6),
    span("engine/device_wait", 13.0, 13.1, 8, 7),
    span("engine/copy_back", 13.1, 13.4, 9, 7, found=0),
    span("engine/tick", 15.1, 18.0, 10, mixed=0, late=1, ahead=0),
    span("engine/wait", 16.0, 16.05, 11, 10),
    span("engine/copy_back", 16.02, 16.05, 12, 11, found=1),
    span("engine/tick", 19.0, 22.5, 13, mixed=0, late=1, ahead=0),
    span("engine/copy_back", 20.0, 20.02, 14, 13, found=1),
    span("engine/tick", 23.0, 26.0, 15, mixed=0, late=0, ahead=0),
    span("engine/copy_back", 24.0, 24.03, 16, 15, found=1),
]


def test_reader_gives_the_value_computed_by_hand():
    assert read("tick_copy_found_share", SERVE) == pytest.approx(60.0)
    # the time the reads took is the older metric's, over the same spans
    assert read("tick_copy_back_ms_p50", SERVE) == pytest.approx(0.03)


def test_reader_gives_none_without_what_it_reads():
    assert read("tick_copy_found_share", []) is None
    # the parent's program under this PR's benchmark files: reads without
    # the attr
    old = [span(s.name, 1e3 * s.start, 1e3 * s.end, s.id, s.parent_id,
                **{k: v for k, v in s.attrs.items() if k != "found"})
           for s in SERVE]
    assert read("tick_copy_found_share", old) is None
    assert read("tick_copy_back_ms_p50", old) == pytest.approx(0.03)


def test_the_manifest_lists_it_with_the_serving_cells():
    bench = harness.load_json("..", "BENCHMARK.json")
    m, = [m for m in bench["per_layer"] if m["name"] == "tick_copy_found_share"]
    reader = harness.load_module("metrics", "tick_copy_found_share")
    assert m == {"name": "tick_copy_found_share", "unit": reader.UNIT,
                 "better": "higher", "source": reader.SOURCE,
                 "layer": reader.LAYER, "moves": reader.MOVES,
                 "workloads": [w["name"] for w in bench["workloads"]
                               if "_serve_" in w["name"]]}
    # every cell it lists reports the end-to-end metric it moves
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved["workloads"])
