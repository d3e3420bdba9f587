"""Find a serving cell's knee: the cell as it is, at other rates.

    python3 benchmark/sweep.py --workload <cell> --rates 3.2,12,25 [--runs 2]
                               [--seconds 45] [--seed 1] [--out <file.jsonl>]

A builder's tool, not part of a run: the benchmark itself offers load at the
rate its traffic file fixes and never searches for one. For every rate, in
the order given, it starts `--runs` untraced runs of the cell, each a process
of its own (this one stays off JAX, so each child gets the chip), with the
mix's `rate_per_s` replaced and nothing else; every run gets another seed.
One table row a run: what the knee is judged by (failed requests, the
requests in the system at each quarter of the window, the drain), the
end-to-end metrics, and the per-layer metrics that need no device trace
(`gen_late_p99_ms`, `slot_occupancy`, `window_stolen_ms`, ...). A rate is past
the knee where a request fails, the backlog grows from quarter to quarter or
the drain takes a second or more; the sweep stops after the first rate at
which every run is past it.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import subprocess   # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def past_knee(row) -> bool:
    q = row.get("in_system_at_quarters") or []
    grows = len(q) == 4 and all(b > a for a, b in zip(q, q[1:])) \
        and q[-1] >= 2 * max(q[0], 1)
    return (row.get("failed") != 0 or grows or row.get("drain_s", 99.0) >= 1.0)


def child(args):
    """One run at one rate, in this process: run.py's path with the mix's
    rate replaced, then the per-layer readers over the same window."""
    sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                            != os.path.join(ROOT, "benchmark")]
    from benchmark import harness
    cell = harness.Cell(args.workload)
    cell.traffic = dict(cell.traffic, rate_per_s=args.child)
    args.trace = 0
    run = cell.loop.run(cell, args, T_PROCESS_START)
    layers = harness.read_metrics(run, "per_layer")
    print("per_layer: " + json.dumps({k: v["value"] for k, v in layers.items()}),
          flush=True)
    harness.emit(run, traced=False)
    return 0


def one_run(args, rate, seed):
    cmd = [sys.executable, os.path.abspath(__file__), "--workload",
           args.workload, "--seconds", str(args.seconds), "--seed", str(seed),
           "--child", repr(rate)]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    row = {"rate_per_s": rate, "seed": seed, "rc": p.returncode}
    for line in p.stdout.splitlines():
        if line.startswith("per_layer: "):
            row["per_layer"] = json.loads(line[len("per_layer: "):])
        elif line.startswith("benchmark: "):
            info = json.loads(line[len("benchmark: "):])
            row.update(info["notes"], setup_parts=info["setup_parts"])
        elif line.startswith('{"correct"'):
            last = json.loads(line)
            row.update(correct=last["correct"], attempted=last["attempted"],
                       failed=last["failed"],
                       **{k: v["value"] for k, v in last["metrics"].items()})
    if p.returncode != 0:
        row["stderr"] = p.stderr[-1500:]
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--runs", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="")
    ap.add_argument("--child", type=float, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child is not None:
        return child(args)
    seed = args.seed
    for rate in (float(r) for r in args.rates.split(",") if r):
        rows = []
        for _ in range(args.runs):
            rows.append(one_run(args, rate, seed))
            seed += 1
            print("sweep: " + json.dumps(rows[-1]), flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rows[-1]) + "\n")
        if all(past_knee(r) for r in rows):
            print(f"sweep: every run at {rate} req/s is past the knee; stopping",
                  flush=True)
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
