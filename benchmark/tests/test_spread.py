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
