"""Read a serving cell's comparison row by row, beside a witness at the stated
precision, its control and planted faults.

    python3 benchmark/witness.py --workload <cell> --seed <n> [--seconds 8]
        [--requests 2] [--control] [--witness] [--faults a,b] [--zero-state]
        [--out chiprun_out/witness]

A builder's tool, not part of a run, for an adapter whose `reference_logits`
shapes the rows it hands the loop (`rows_kept`, `held_rows`,
`at_stated_precision`, `planted`: benchmark/models/lfm2.py). Like
`benchmark/control.py` it runs the cell's own loop over a short window, which
ends in the loop's own `_check` over `--requests` finished requests, and calls
that same `_check` again on the same requests; every reading is one line

    <tag>: {"passes", "worst_logit_gap" (what the loop read), "limit",
            "rows", "q50" "q75" "q90" "q99" "worst" "mean" "off_share"
            (of the gaps as the reference gave them, before `held_rows`),
            "per_request": [[rows, q90, worst], ...]}

and the gaps themselves go to `<out>_<seed>.npz` under the tag. The tags:
`clean` (the cell as it is); `control` (the reference one precision below:
has to fail); `witness` (the reference's own equations AT the stated
precision, teacher-forced on the program's tokens: its largest logit a row is
read against the float32 rows as if the program had emitted it; it has to read
like `clean`, or what `clean` reads is not the precision's doing);
`fault:<name>` (a fault planted in the reference, which from the comparison's
side is a program that lacks it: has to fail). `--zero-state` plants one in
the PROGRAM: every conv-state snapshot the set-up wrote is zeroed before the
window opens, so each request that starts from a shared preamble resumes from
a zero state.
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


def summary(gaps):
    import numpy as np
    g = np.concatenate(gaps)
    q = np.quantile(g, [0.5, 0.75, 0.9, 0.99])
    return {"rows": len(g), "q50": float(q[0]), "q75": float(q[1]),
            "q90": float(q[2]), "q99": float(q[3]), "worst": float(g.max()),
            "mean": float(g.mean()), "off_share": float((g > OFF).mean()),
            "per_request": [[len(x), float(np.quantile(x, 0.9)),
                             float(x.max())] for x in gaps]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--zero-state", action="store_true")
    ap.add_argument("--out", default="chiprun_out/witness")
    args = ap.parse_args(argv)
    args.trace = 0
    sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                            != os.path.join(ROOT, "benchmark")]
    import numpy as np
    from benchmark import harness
    cell = harness.Cell(args.workload)
    cell.spec = dict(cell.spec, check_requests=args.requests)
    loop, adapter = cell.loop, cell.adapter

    kept, check = {}, loop._check

    def keeping(cell, scope, handles, load, out):
        kept.update(scope=scope, handles=handles, load=load)
        return check(cell, scope, handles, load, out)
    loop._check = keeping

    if args.zero_state:
        build = adapter.build_engine

        def zeroing(cfg, spec, scope):
            """The engine, whose first submit AFTER the set-up's (one warm
            request a system prompt) zeroes the snapshots first."""
            import jax.numpy as jnp
            engine = build(cfg, spec, scope)
            submit, n_warm = engine.submit, cell.traffic.get(
                "system_prompts", {}).get("count", 1)
            seen = [0]

            def planted(*a, **k):
                seen[0] += 1
                if seen[0] == n_warm + 1:
                    name = engine._cache_prefix + "_conv_block"
                    snaps = engine.scope.get(name)
                    print(f"zero-state: {name} {snaps.shape} zeroed after "
                          f"{n_warm} warm requests", flush=True)
                    engine.scope.set_var(name, jnp.zeros_like(snaps))
                return submit(*a, **k)
            engine.submit = planted
            return engine
        adapter.build_engine = zeroing

    adapter.rows_kept = []
    dump = {}

    def report(tag, ok, run):
        """One line for the rows `rows_kept` took since the last report."""
        value, limit = run.checks["worst_logit_gap"]
        gaps = []
        for seq, rows in adapter.rows_kept:
            h = next(h for h in kept["handles"]
                     if h.done and h.prompt + h.tokens[:-1] == seq)
            _, gap = adapter.held_rows(rows.copy(), np.asarray(h.tokens),
                                       1.0, 0.0)
            gaps.append(gap)
        print(tag + ": " + json.dumps(dict(
            {"passes": bool(ok), "worst_logit_gap": value, "limit": limit},
            **summary(gaps))), flush=True)
        for k, gap in enumerate(gaps):
            dump[f"{tag}.{k}"] = gap
        taken = list(adapter.rows_kept)
        del adapter.rows_kept[:]
        return taken

    run = loop.run(cell, args, T_PROCESS_START)
    print("run: " + json.dumps({
        "failed": run.failed, "attempted": run.attempted,
        "drain_s": run.notes.get("drain_s")}), flush=True)
    clean_rows = report("clean", run.correct, run)
    config = cell.config

    def again(tag, cfg_ctx, n=None):
        below = harness.Run(cell, args.seed, args.seconds, run.device)
        with cfg_ctx as cfg:
            cell.config = cfg
            if n is not None:
                cell.spec = dict(cell.spec, check_requests=n)
            t = time.perf_counter()
            ok = check(cell, kept["scope"], kept["handles"], kept["load"],
                       below)
            print(f"{tag}: {time.perf_counter() - t:.1f} s", flush=True)
        cell.config = config
        cell.spec = dict(cell.spec, check_requests=args.requests)
        report(tag, ok, below)
        return ok

    verdict = bool(run.correct)
    if args.zero_state:
        verdict = not verdict           # the planted fault has to be refused
    if args.control:
        verdict &= not again("control", adapter.one_precision_below(config))
    for fault in filter(None, args.faults.split(",")):
        verdict &= not again("fault:" + fault, adapter.planted(
            fault, config, kept["scope"]), n=1)
    if args.witness:
        params = {n: kept["scope"].get(n)
                  for n in adapter.param_names(config)}
        gaps, agree, t = [], [], time.perf_counter()
        with adapter.at_stated_precision(config) as cfg:
            for seq, rows in clean_rows:
                h = next(h for h in kept["handles"]
                         if h.done and h.prompt + h.tokens[:-1] == seq)
                first = len(h.prompt) - 1
                own = adapter.envelope_logits(
                    cfg, params, np.asarray(seq, np.int32),
                    cell.spec["engine"]["max_len"])[first:].argmax(-1)
                _, gap = adapter.held_rows(rows.copy(), own, 1.0, 0.0)
                gaps.append(gap)
                agree.append(float((own == np.asarray(h.tokens)).mean()))
        held, echo = (float(config.get("check_rows_held", 1.0)),
                      float(config.get("check_echo", 0.0)))
        as_loop = max(max(float(np.quantile(g, held)), float(g.max()) - echo)
                      for g in gaps)
        print("witness: " + json.dumps(dict(
            {"seconds": time.perf_counter() - t,
             "as_the_loop_would_read_it": as_loop,
             "limit": cell.spec["logit_gap_tol"],
             "same_token_as_the_program": agree}, **summary(gaps))),
            flush=True)
        for k, gap in enumerate(gaps):
            dump[f"witness.{k}"] = gap
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    np.savez(f"{args.out}_{args.seed}.npz", **dump)
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
