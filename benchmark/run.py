"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the accelerator this process finds, and
prints as its last line of standard output the JSON object the driver reads.
One process, which holds the chip(s); no child is started. Exits non-zero,
with no result, where JAX finds no TPU, fewer chips than the cell asks for,
or a device the table of peaks does not know.
"""

import time

T_PROCESS_START = time.perf_counter()     # set-up is counted from here

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed is a whole number >= 0 and --seconds is positive")

    # import `benchmark` and `paddle_tpu` from this checkout, and nothing
    # from the benchmark's own directory by a bare name
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") != os.path.join(ROOT, "benchmark")]
    from benchmark import harness
    cell = harness.Cell(args.workload)
    run = cell.loop.run(cell, args, T_PROCESS_START)
    harness.emit(run, traced=bool(args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
