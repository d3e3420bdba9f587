"""The spread a bound is set from: quartile distance over the median."""

import json

import pytest

from benchmark import spread


def test_spread_is_interquartile_over_median(tmp_path):
    # statistics.quantiles of 1..6 (exclusive method): q1 = 1.75, q3 = 5.25
    assert spread.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    log = tmp_path / "set.log"
    lines = ["## a header", "benchmark: {}"]
    for v in (100.0, 101.0, 99.0, 100.5, 100.2, 99.8):
        lines.append(json.dumps({"correct": True, "attempted": 1, "failed": 0,
                                 "metrics": {"m": {"value": v, "unit": "x"}},
                                 "device": {}}))
    log.write_text("\n".join(lines) + "\n")
    values = spread.read_set(str(log))
    assert values == {"m": [100.0, 101.0, 99.0, 100.5, 100.2, 99.8]}
    assert spread.spread(values["m"]) == pytest.approx(1.025 / 100.1, rel=1e-6)
    assert spread.main([str(log)]) == 0


def quartile_distance(values):
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def test_check_spread_leaves_out_the_farthest_run_where_that_narrows_it():
    # one run far off: 1..5 and 60. Of all six the quartiles are 1.75 and
    # 18.75; of 1..5 they are 1.5 and 4.5; the median of all six is 3.5
    six = [3, 1, 60, 5, 2, 4]
    assert quartile_distance(six) == pytest.approx(17.0)
    assert spread.check_spread(six) == pytest.approx(3.0 / 3.5)
    # the plain statistic keeps the far run
    assert spread.spread(six) == pytest.approx(17.0 / 3.5)
    # the run left out is the one farthest from the MEDIAN, not the largest:
    # of -50, 1..5 it is -50
    assert spread.check_spread([3, 1, -50, 5, 2, 4]) == pytest.approx(
        quartile_distance([1, 2, 3, 4, 5]) / 2.5)
    # where leaving it out does not narrow the distance, all six count: with
    # no spread at all both read 0, and it is never over the plain one
    assert spread.check_spread([7.0] * 6) == 0.0
    for six in ([1, 2, 3, 4, 5, 6], [0, 0, 0.2, 9.8, 10, 10.3],
                [100, 101, 99, 100.5, 100.2, 99.8]):
        assert spread.check_spread(six) <= spread.spread(six)


@pytest.mark.parametrize("n,count", [(6, 1), (7, 7), (8, 28), (12, 924),
                                     (18, 18564)])
def test_draws_of_six_are_counted_exactly(n, count):
    values = [100.0 + i for i in range(n)]
    it, total, exact = spread.draws(values)
    assert exact and total == count
    seen = {tuple(sorted(d)) for d in it}
    assert len(seen) == count and all(len(d) == 6 for d in seen)


def test_many_runs_are_sampled_from_a_fixed_seed():
    values = [100.0 + i for i in range(40)]        # C(40, 6) = 3,838,380
    a, n, exact = spread.draws(values)
    b, _, _ = spread.draws(values)
    assert not exact and n == spread.MAX_DRAWS
    assert [next(a) for _ in range(5)] == [next(b) for _ in range(5)]


def test_over_draws_reads_median_p95_and_the_share_under_half_a_bound():
    # seven runs, six alike and one far off: of the 7 draws of six, one
    # leaves the far run out (0) and six hold it and drop it again (0)
    d = spread.over_draws([10.0] * 6 + [20.0], bound=0.05)
    assert d == {"draws": 7, "exact": True, "median": 0.0, "p95": 0.0,
                 "under_half_bound": 1.0}
    # 1..8: every draw's statistic by hand is check_spread of that draw
    values = [1.0, 2, 3, 4, 5, 6, 7, 8]
    import itertools
    by_hand = sorted(spread.check_spread(list(c))
                     for c in itertools.combinations(values, 6))
    d = spread.over_draws(values, bound=1.2)
    assert d["draws"] == 28 and d["p95"] == by_hand[26]     # ceil(.95 * 28) = 27th
    assert d["under_half_bound"] == sum(s < 0.6 for s in by_hand) / 28


def test_main_prints_the_draws_against_the_manifests_bounds(tmp_path, capsys):
    logs = []
    for k in range(2):
        log = tmp_path / f"set{k}.log"
        log.write_text("\n".join(
            json.dumps({"correct": True, "attempted": 1, "failed": 0,
                        "metrics": {"ttft_p90_ms": {"value": 10.0 + 0.01 * i + k,
                                                    "unit": "ms"}},
                        "device": {}}) for i in range(6)) + "\n")
        logs.append(str(log))
    bench = tmp_path / "BENCHMARK.json"
    bench.write_text(json.dumps({"end_to_end": [
        {"name": "ttft_p90_ms", "bound": 0.1}]}))
    assert spread.main(["--bounds", str(bench)] + logs) == 0
    out = capsys.readouterr().out
    assert "all n=12" in out and "924 draws of 6" in out
    assert "under half the bound 0.1 in" in out
