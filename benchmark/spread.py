"""Medians and spreads of sets of runs, as the bounds are set from them.

    python3 benchmark/spread.py [--bounds BENCHMARK.json] [--cold-first] <log> [<log> ...]

Each log holds the last lines of runs of ONE cell (other lines are skipped);
each log is one set. Two statistics:

- `spread`: the distance between the first and third quartile
  (statistics.quantiles(values, n=4)) as a share of the median. The contract
  sets a bound to about five times the widest spread of a metric over the
  sets and cells, and never under 1%; the driver calls a bound too loose
  where it is over eight times this.
- `check_spread`: what the driver holds against a bound in a check of six
  runs a side: the same distance with the run farthest from the median left
  out where that narrows it, over the median of all six. Where it is over
  half the bound, the check cannot tell a loss from noise and the PR comes
  back `unresolved`. Over ALL the runs of the logs together, every draw of
  six is one check that could have happened (runs of several calls, since
  machines differ): the median and the 95th percentile over the draws are
  printed, and with `--bounds` the share of draws that read under half the
  metric's bound. A bound of twice that 95th percentile passes nineteen
  checks in twenty.

With `--cold-first` each log's first run compiled (a call that began with an
empty cache): its `setup_s` is left out, as the driver keeps each side's
first run apart.
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import random
import statistics
import sys

DRAW = 6
MAX_DRAWS = 200_000     # beyond this many, a seeded sample of the draws


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


def check_spread(values):
    """The driver's statistic on one set: quartile distance over the median,
    the run farthest from the median left out only where that narrows it."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = [v for i, v in enumerate(values) if i != far]
    q1, _, q3 = statistics.quantiles(values, n=4)
    r1, _, r3 = statistics.quantiles(rest, n=4)
    return min(q3 - q1, r3 - r1) / med


def draws(values, k=DRAW):
    """Every k-subset of the runs, or, past MAX_DRAWS of them, a sample
    drawn from a fixed seed. Returns (iterator, count, exact)."""
    n = math.comb(len(values), k)
    if n <= MAX_DRAWS:
        return itertools.combinations(values, k), n, True
    rng = random.Random(len(values))
    return (rng.sample(values, k) for _ in range(MAX_DRAWS)), MAX_DRAWS, False


def over_draws(values, bound=None):
    """check_spread over the draws of six: its median, its 95th percentile,
    the count of draws, and the share of them under half of `bound`."""
    it, n, exact = draws(values)
    stats = sorted(check_spread(list(d)) for d in it)
    out = {"draws": n, "exact": exact, "median": statistics.median(stats),
           "p95": stats[min(n - 1, math.ceil(0.95 * n) - 1)]}
    if bound is not None:
        out["under_half_bound"] = sum(s < bound / 2 for s in stats) / n
    return out


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bounds", default="", help="BENCHMARK.json to read "
                    "each end-to-end metric's bound from")
    ap.add_argument("--cold-first", action="store_true", help="leave each "
                    "log's first run out of setup_s")
    ap.add_argument("logs", nargs="+")
    args = ap.parse_args(argv)
    bounds = {}
    if args.bounds:
        with open(args.bounds) as f:
            bounds = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    sets = [read_set(p) for p in args.logs]
    if args.cold_first:
        for s in sets:
            s["setup_s"] = s.get("setup_s", [])[1:]
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
        every = [x for s in sets for x in s.get(name, [])]
        if len(every) >= DRAW:
            d = over_draws(every, bounds.get(name))
            text = (f"{'':28s}  all n={len(every)} median="
                    f"{statistics.median(every):.6g} spread="
                    f"{100 * spread(every):.3f}%; check_spread over "
                    f"{d['draws']} draws of {DRAW}"
                    f"{'' if d['exact'] else ' (sampled)'}: median "
                    f"{100 * d['median']:.3f}% p95 {100 * d['p95']:.3f}%")
            if "under_half_bound" in d:
                text += (f"; under half the bound {bounds[name]:g} in "
                         f"{100 * d['under_half_bound']:.1f}% of draws")
            print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
