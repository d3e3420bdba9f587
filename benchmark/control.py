"""Hold a serving cell's comparison against its control.

    python3 benchmark/control.py --workload <cell> --seed <n> [--seconds 8]
                                 [--requests 3] [--margin <m>]

A builder's tool, not part of a run. It runs the cell's own loop over a short
window (`--seconds` of the cell's mix at the cell's rate), which ends in the
loop's own `_check` over `--requests` finished requests, and then calls that
same `_check`, on the same requests, with the reference computed in the
nearest precision below the one the configuration states
(`adapter.one_precision_below`). A limit is sound where the first reading
passes it and the second fails it; both readings are printed beside the
limit, and the exit code is 0 only then.

Where the adapter's reference follows more than one path (`envelope_detail`),
every scored row is also read WITHOUT them, and each row the plain forward
puts off by more than `OFF` is printed with the narrowest path that explains
it: the count of selections that differ, shown and not asserted. `--margin`
replaces the configuration's `router_tie_margin` (a wider one shows which
margin the rows need).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OFF = 0.05
MARGINS = (0.005, 0.01, 0.02, 0.03, 0.05, 0.1)


def rows_read(detail, tokens, first):
    """Per scored row of the envelope's window (row r emitted tokens[r + 1],
    scored from `first` on): its gap on the plain forward, and the gap left
    when paths no wider than each of `MARGINS` may explain it."""
    lo, hi = detail["rows"]
    out = []
    for r in range(max(lo, first), min(hi, len(tokens) - 1)):
        row, tok = detail["plain"][r - lo], tokens[r + 1]
        sd = float(row.std())
        plain = float(row.max() - row[tok]) / sd
        mine = detail["at"] == r
        below = -detail["paths"][mine, tok] / sd
        left = [min([plain] + below[detail["wide"][mine] <= m].tolist())
                for m in MARGINS]
        fits = detail["wide"][mine][below <= OFF]
        out.append({"row": r, "plain": plain, "left": left,
                    "paths": int(mine.sum()),
                    "narrowest": float(fits.min()) if len(fits) else None})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--margin", type=float)
    args = ap.parse_args(argv)
    args.trace = 0
    sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                            != os.path.join(ROOT, "benchmark")]
    from benchmark import harness
    cell = harness.Cell(args.workload)
    cell.spec = dict(cell.spec, check_requests=args.requests)
    if args.margin is not None:
        cell.config = dict(cell.config, router_tie_margin=args.margin)
    loop, adapter = cell.loop, cell.adapter

    kept, check = {}, loop._check

    def keeping(cell, scope, handles, load, out):
        kept.update(scope=scope, handles=handles, load=load)
        return check(cell, scope, handles, load, out)
    loop._check = keeping

    reads, reference = [], adapter.reference_logits
    if hasattr(adapter, "envelope_detail"):
        adapter.envelope_detail = {}

        def reading(cfg, params, tokens, pad_to):
            t = time.perf_counter()
            ref = reference(cfg, params, tokens, pad_to)
            print(f"reference: {len(tokens)} tokens, "
                  f"{time.perf_counter() - t:.1f} s, "
                  f"{len(adapter.envelope_detail.get('at', ()))} paths",
                  flush=True)
            tokens = [int(t) for t in tokens]
            first = next(len(h.prompt) - 1 for h in kept["handles"]
                         if h.prompt + h.tokens[:-1] == tokens)
            if adapter.envelope_detail:
                reads.extend(rows_read(adapter.envelope_detail, tokens, first))
                adapter.envelope_detail.clear()
            return ref
        adapter.reference_logits = reading

    def report(tag, ok, run):
        value, limit = run.checks["worst_logit_gap"]
        off = [r for r in reads if r["plain"] > OFF]
        print(tag + ": " + json.dumps({
            "passes": ok, "worst_logit_gap": value, "limit": limit,
            "rows": len(reads), "plain_worst": max(
                [r["plain"] for r in reads], default=None),
            "plain_off": len(off),
            "left_worst_by_margin": {
                str(m): max(r["left"][k] for r in reads)
                for k, m in enumerate(MARGINS)} if reads else None,
            "off_rows": [{"row": r["row"], "plain": round(r["plain"], 3),
                          "paths": r["paths"], "narrowest": r["narrowest"]}
                         for r in off]}), flush=True)
        del reads[:]

    run = loop.run(cell, args, T_PROCESS_START)
    print("memory: " + json.dumps(
        {"peak_before_reference": getattr(adapter, "peak_before_reference",
                                          None),
         "peak": harness.memory_peak_bytes(run.device["devices"])}))
    clean = bool(run.correct)
    report("clean", clean, run)
    below = harness.Run(cell, args.seed, args.seconds, run.device)
    with adapter.one_precision_below(cell.config) as cfg:
        cell.config = cfg
        control = check(cell, kept["scope"], kept["handles"], kept["load"],
                        below)
    report("control", control, below)
    return 0 if clean and not control else 1


if __name__ == "__main__":
    sys.exit(main())
