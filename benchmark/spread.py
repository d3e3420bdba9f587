"""Medians and spreads of a set of runs, as the bounds are set from them.

    python3 benchmark/spread.py <log> [<log> ...]

Each log holds the last lines of runs of ONE cell (other lines are skipped);
each log is one set. A spread is the distance between the first and third
quartile (statistics.quantiles(values, n=4)) as a share of the median; a
bound is about five times the widest spread of a metric over the sets and
cells, and never under 1%.
"""

from __future__ import annotations

import json
import statistics
import sys


def read_set(path):
    values = {}
    with open(path) as f:
        for line in f:
            if not line.startswith('{"correct"'):     # a run's last line
                continue
            run = json.loads(line)
            if not run["correct"]:
                print(f"{path}: a run is not correct", file=sys.stderr)
            for name, m in run["metrics"].items():
                values.setdefault(name, []).append(m["value"])
    return values


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths):
    sets = [read_set(p) for p in paths]
    for name in sorted({n for s in sets for n in s}):
        row = [f"{name:28s}"]
        for s in sets:
            v = s.get(name, [])
            if len(v) >= 2:
                row.append(f"n={len(v)} median={statistics.median(v):.6g} "
                           f"spread={100 * spread(v):.3f}%")
            else:
                row.append(f"n={len(v)}")
        print("  |  ".join(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
