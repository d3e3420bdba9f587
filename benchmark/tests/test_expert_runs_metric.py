"""`expert_runs_p50` (PR 51) on a hand-made run against the value computed by
hand, on runs without what it reads (PTPU_TRACE=0; the parent's program,
whose ticks have no `expert_runs`): None; and its entry in the manifest."""

from benchmark import harness
from test_span_metrics import read, span

# three decode ticks and a mixed one (a lane in prefill touches nearly every
# expert in one run a layer: the median is the decode ticks')
SERVE = [
    span("engine/tick", 0.0, 9.0, 1, prefill=1, experts_touched=60,
         expert_runs=6),
    span("engine/tick", 10.0, 12.0, 2, prefill=0, experts_touched=21,
         expert_runs=15),
    span("engine/tick", 12.1, 15.0, 3, prefill=0, experts_touched=24,
         expert_runs=19),
    span("engine/tick", 15.1, 18.0, 4, prefill=0, experts_touched=19,
         expert_runs=16),
]


def test_reader_gives_the_value_computed_by_hand():
    assert read("expert_runs_p50", SERVE) == 16.0
    # a run is at least one touched expert
    assert read("expert_runs_p50", SERVE) <= read("experts_touched_p50", SERVE)


def test_reader_gives_none_without_what_it_reads():
    assert read("expert_runs_p50", []) is None
    # the parent's program under this PR's benchmark files: ticks without
    # the attr
    old = [span(s.name, 1e3 * s.start, 1e3 * s.end, s.id, s.parent_id,
                **{k: v for k, v in s.attrs.items() if k != "expert_runs"})
           for s in SERVE]
    assert read("expert_runs_p50", old) is None
    assert read("experts_touched_p50", old) == 21.0


def test_the_manifest_lists_it_with_the_routed_serving_cells():
    bench = harness.load_json("..", "BENCHMARK.json")
    m, = [m for m in bench["per_layer"] if m["name"] == "expert_runs_p50"]
    reader = harness.load_module("metrics", "expert_runs_p50")
    touched, = [t for t in bench["per_layer"]
                if t["name"] == "experts_touched_p50"]
    assert m == {"name": "expert_runs_p50", "unit": reader.UNIT,
                 "better": "lower", "source": reader.SOURCE,
                 "layer": reader.LAYER, "moves": reader.MOVES,
                 "workloads": touched["workloads"]}
    # every cell it lists reports the end-to-end metric it moves
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved["workloads"])
