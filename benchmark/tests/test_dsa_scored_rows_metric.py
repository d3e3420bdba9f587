"""`dsa_scored_rows_share` (PR 64) on a hand-made run against the quotient
computed by hand, on runs without what it reads (PTPU_TRACE=0; the parent's
program, whose ticks carry `dsa_rows` and no `dsa_scored_rows`): None; and its
entry in the manifest, beside `dsa_selected_share`'s."""

import pytest

from benchmark import harness
from test_span_metrics import read, span

# five sparse layers; three decode ticks of 7, 9 and 0 live rows of 64 (whole
# steps of 8: 8, 16 and 0) and a mixed tick of 7 + 128 live rows of 320 (136)
SERVE = [
    span("engine/tick", 0.0, 7.0, 1, prefill=0, dsa_rows=35,
         dsa_scored_rows=40),
    span("engine/tick", 7.1, 36.0, 2, prefill=1, dsa_rows=675,
         dsa_scored_rows=680),
    span("engine/tick", 36.1, 43.0, 3, prefill=0, dsa_rows=45,
         dsa_scored_rows=80),
    span("engine/tick", 43.1, 49.0, 4, prefill=0, dsa_rows=0,
         dsa_scored_rows=0),
    span("engine/admit", 49.0, 49.1, 5, pending=0),
]


def test_reader_gives_the_quotient_computed_by_hand():
    assert read("dsa_scored_rows_share", SERVE) == pytest.approx(
        100 * (40 + 680 + 80) / (35 + 675 + 45))
    # a selection sorts at least the rows that hold a token
    assert read("dsa_scored_rows_share", SERVE) >= 100


def test_reader_gives_none_without_what_it_reads():
    assert read("dsa_scored_rows_share", []) is None
    # the parent's program under this PR's benchmark files: ticks with the
    # sparse read's rows and without the rows sorted
    old = [span(s.name, 1e3 * s.start, 1e3 * s.end, s.id, s.parent_id,
                **{k: v for k, v in s.attrs.items()
                   if k != "dsa_scored_rows"}) for s in SERVE]
    assert read("dsa_scored_rows_share", old) is None
    # ticks that held no row at all: nothing to divide by
    assert read("dsa_scored_rows_share", SERVE[3:]) is None
    # a span that lacks either count is left out, the others read
    assert read("dsa_scored_rows_share", SERVE[:1] + old[1:]) \
        == pytest.approx(100 * 40 / 35)


def test_the_manifest_lists_it_with_the_sparse_reads_cell():
    bench = harness.load_json("..", "BENCHMARK.json")
    m = bench["per_layer"][-1]          # appended: nothing before it moved
    reader = harness.load_module("metrics", "dsa_scored_rows_share")
    selected, = [t for t in bench["per_layer"]
                 if t["name"] == "dsa_selected_share"]
    assert m == {"name": "dsa_scored_rows_share", "unit": reader.UNIT,
                 "better": "lower", "source": reader.SOURCE,
                 "layer": reader.LAYER, "moves": reader.MOVES,
                 "workloads": selected["workloads"]}
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved["workloads"])
    cell = harness.Cell(m["workloads"][0])
    assert "dsa_scored_rows_share" in {p["name"]
                                       for p in cell.metrics["per_layer"]}
