"""One run of a serving cell at another rate, read for WAITING.

    python3 benchmark/waits.py --workload <cell> --rate 7 --seed <n>
                               [--seconds 45]

A builder's tool beside `benchmark/sweep.py`, for a cell whose mix's longest
answers outlast the window by their own length, so that sweep.py's third
criterion (a drain under a second) fails at every load: the run sweep.py's
child makes (the cell as it is, the mix's `rate_per_s` replaced), without the
comparison after it (a sweep reads no `correct`, and the reference's forwards
are most of such a run), and one line `waits: {...}` of what the drain stands
for. Per request: `queue_wait` (admitted - due: the time it waited for a slot
or for blocks; a request that never waits is delayed by no backlog) and
`late` (done - due less its own tokens at the run's median time per token: what
the load added to its life). Beside them sweep.py's own three readings
(`failed`, `in_system_at_quarters`, `drain_s`) and `drain_of_the_last`: the
request that finished last, its tokens, when it was due and how long it
waited, which says whether the drain is that request's length or a backlog.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)
    args.trace = 0
    sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                            != os.path.join(ROOT, "benchmark")]
    from benchmark import harness
    cell = harness.Cell(args.workload)
    cell.traffic = dict(cell.traffic, rate_per_s=args.rate)
    cell.loop._check = lambda *a: True
    run = cell.loop.run(cell, args, T_PROCESS_START)
    q = harness.quantile
    done = [r for r in run.requests if r["ok"]]
    wait = [1e3 * (r["admitted"] - r["due"]) for r in done]
    ttft = [1e3 * (r["first"] - r["due"]) for r in done]
    tpot = [1e3 * (r["done"] - r["first"]) / (r["n_out"] - 1) for r in done
            if r["n_out"] > 1]
    per_token = q(tpot, 0.5)
    late = [1e3 * (r["done"] - r["due"]) - r["n_out"] * per_token
            for r in done]
    last = max(done, key=lambda r: r["done"])
    # the window opened `drain_s` + `--seconds` before the last one finished
    t_open = last["done"] - run.notes["drain_s"] - args.seconds
    print("waits: " + json.dumps({
        "rate_per_s": args.rate, "seed": args.seed,
        "requests": run.attempted, "failed": run.failed,
        "in_system_at_quarters": run.notes["in_system_at_quarters"],
        "drain_s": run.notes["drain_s"],
        "queue_wait_ms": {"p50": q(wait, 0.5), "p99": q(wait, 0.99),
                          "max": max(wait)},
        "late_ms": {"p50": q(late, 0.5), "p99": q(late, 0.99),
                    "max": max(late)},
        "ttft_p50_ms": q(ttft, 0.5), "ttft_p90_ms": q(ttft, 0.9),
        "tpot_p50_ms": per_token, "tick_ms_p50": run.notes["tick_ms_p50"],
        "slot_occupancy": 100.0 * run.counters["busy_slot_ticks_window"]
        / max(run.counters["total_slot_ticks_window"], 1),
        "drain_of_the_last": {
            "n_out": last["n_out"], "due_s": last["due"] - t_open,
            "queue_wait_ms": 1e3 * (last["admitted"] - last["due"])},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
