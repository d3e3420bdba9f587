"""`tick_late_read_share` (PR 44) on a hand-made run against the value computed
by hand, on runs without what it reads (PTPU_TRACE=0; the parent's program,
whose ticks have no `late`): None; and its entry in the manifest."""

import pytest

from benchmark import harness
from test_span_metrics import read, span

# five engine steps: a mixed tick that delivers a first token (read at once),
# three decode ticks read a launch late, the copy back and the commit of each
# under the NEXT tick, and the last tick before the engine idles
SERVE = [
    span("engine/tick", 0.0, 9.0, 1, mixed=1, late=0),
    span("engine/commit", 9.0, 9.2, 2),
    span("engine/tick", 10.0, 13.0, 3, mixed=0, late=1),
    span("engine/tick", 14.0, 17.0, 4, mixed=0, late=1),
    span("engine/copy_back", 15.0, 15.3, 5, 4),
    span("engine/commit", 15.3, 15.4, 6, 4),
    span("engine/tick", 18.0, 21.0, 7, mixed=0, late=1),
    span("engine/copy_back", 19.0, 19.3, 8, 7),
    span("engine/tick", 22.0, 25.5, 9, mixed=0, late=0),
    span("engine/copy_back", 23.0, 23.3, 10, 9),
    span("engine/finish", 25.6, 25.7, 11),
]


def test_reader_gives_the_value_computed_by_hand():
    assert read("tick_late_read_share", SERVE) == pytest.approx(60.0)
    eager = [span(s.name, 1e3 * s.start, 1e3 * s.end, s.id, s.parent_id,
                  **dict(s.attrs, late=0)) if s.name == "engine/tick" else s
             for s in SERVE]
    assert read("tick_late_read_share", eager) == 0.0


def test_reader_gives_none_without_what_it_reads():
    assert read("tick_late_read_share", []) is None
    # the parent's program under this PR's benchmark files: ticks without
    # the attr
    old = [span(s.name, 1e3 * s.start, 1e3 * s.end, s.id, s.parent_id,
                **{k: v for k, v in s.attrs.items() if k != "late"})
           for s in SERVE]
    assert read("tick_late_read_share", old) is None


def test_the_manifest_lists_it_last_with_the_four_serving_cells():
    bench = harness.load_json("..", "BENCHMARK.json")
    m = bench["per_layer"][-1]
    reader = harness.load_module("metrics", "tick_late_read_share")
    assert m == {"name": "tick_late_read_share", "unit": reader.UNIT,
                 "better": "higher", "source": reader.SOURCE,
                 "layer": reader.LAYER, "moves": reader.MOVES,
                 "workloads": [w["name"] for w in bench["workloads"]
                               if "_serve_" in w["name"]]}
    assert len(m["workloads"]) == 4
    # every cell it lists reports the end-to-end metric it moves
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved["workloads"])
