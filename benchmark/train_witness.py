"""Hold a TRAINING cell's comparison against its reference, its control and
planted faults, finer than the loop's one loss.

    python3 benchmark/train_witness.py --workload <cell> --seed <n>
        [--steps 0] [--control] [--faults a,b] [--update]
        [--grad-norm-tol 0.02] [--update-norm-tol 0.01]
        [--out chiprun_out/train_witness]

A builder's tool, not part of a run (benchmark/control.py and witness.py do
this for the serving loops, whose check is `_check`; the training loop's is
one loss). It builds the cell's own program (the adapter's `build_train`,
Adam, `Executor`), runs the startup program under `--seed`, `--steps` training
steps over the cell's ring (0: none; a window's worth shows the comparison
where the loop makes it, on weights that have begun to learn the ring by
heart) and then ONE step on the ring's next batch, with the loss and every
parameter's gradient NORM computed in the step's own graph (no gradient
leaves the device), and reads beside them what the adapter's reference gives
on the weights that step started from (`reference_grads`): one line a
reading,

    <tag>: {"loss", "loss_rel_err", "loss_tol", "grad_norm_rel_err":
            {group: [a layer]}, "worst_grad_norm", "grad_norm_tol", "passes"}

`clean` (the reference as it is: has to pass both tolerances); `control` (the
reference with every matmul one precision down, `one_precision_below`: has to
fail one of them); `fault:<name>` (`planted`: a fault on the reference's side,
from the comparison's side a program that lacks the mechanism: has to fail
one). `--update` (with `--steps 0`: the formula is the first step's, from zero
moments) also compares the norm of ONE Adam step's change a group of
parameters (the program's, from a host copy of the weights; the reference's
from its own gradients through Adam's first-step formula) under
`--update-norm-tol`: a state left unchanged reads 1. Exit 0 only if every
reading came out as it has to.

The loss's tolerance is the cell's `loss_rel_tol`, the one a run enforces.
The two on the norms are this tool's own, since no run reads them (PERF.md
section 6, PR 50, has the readings they lie between).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--update", action="store_true")
    ap.add_argument("--grad-norm-tol", type=float, default=0.02)
    ap.add_argument("--update-norm-tol", type=float, default=0.01)
    ap.add_argument("--out", default="chiprun_out/train_witness")
    args = ap.parse_args(argv)
    if args.update and args.steps:
        ap.error("--update compares Adam's FIRST step: it goes with --steps 0")
    sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                            != os.path.join(ROOT, "benchmark")]
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    from benchmark import harness, traffic

    cell = harness.Cell(args.workload)
    cfg, mix, adapter = cell.config, cell.traffic, cell.adapter
    names = adapter.param_names(cfg)
    with pt.core.unique_name.guard():
        loss = adapter.build_train(cfg, mix)
        opt = mix["optimizer"]
        optimizer = pt.optimizer.AdamOptimizer(
            learning_rate=opt["learning_rate"])
        optimizer.minimize(loss)
        block = pt.default_main_program().global_block()
        norms = [layers.sqrt(layers.reduce_sum(layers.square(
            layers.cast(block.var(n + "@GRAD"), "float32")))) for n in names]
    ring = traffic.train_batches(mix, args.seed, cell.chips,
                                 adapter.vocabs(cfg))
    batch = ring[args.steps % len(ring)]
    pt.default_startup_program().random_seed = args.seed % (2 ** 31 - 1) + 1
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.global_scope()
    t = time.perf_counter()
    trained = [float(np.asarray(exe.run(
        feed=ring[i % len(ring)]["feed"], fetch_list=[loss] + norms)[0]))
        for i in range(args.steps)]
    if trained:
        print(f"trained: {args.steps} steps in {time.perf_counter() - t:.1f} "
              f"s, losses {trained[0]:.4f} " + " ".join(
                  f"{x:.4f}" for x in trained[49::50]) + f" {trained[-1]:.4f}",
              flush=True)
    params = {n: scope.get(n) for n in names}
    before = {n: np.asarray(v) for n, v in params.items()}   # on the host
    t = time.perf_counter()
    ref_loss, ref_grads = adapter.reference_grads(cfg, params, batch)
    print(f"reference: {time.perf_counter() - t:.1f} s", flush=True)
    ref_norms = adapter.group_norms(ref_grads, cfg)
    ref_update = None
    if args.update:
        # Adam's first step from zero moments: lr * g / (|g| + eps / sqrt(1 -
        # beta2)) (ops/optimizer_ops.py `_adam`)
        lr, b2, eps = opt["learning_rate"], 0.999, 1e-8
        ref_update = adapter.group_norms(
            {n: lr * g / (jnp.abs(g) + eps / (1 - b2) ** 0.5)
             for n, g in ref_grads.items()}, cfg)
    del ref_grads, params
    t = time.perf_counter()
    out = exe.run(feed=batch["feed"], fetch_list=[loss] + norms)
    print(f"step: {time.perf_counter() - t:.1f} s, memory peak "
          f"{harness.memory_peak_bytes(jax.devices()[:1]) / 1e9:.2f} GB",
          flush=True)
    got_loss = float(np.asarray(out[0]))
    got = adapter.group_norms(
        {n: np.asarray(v).reshape(()) for n, v in zip(names, out[1:])}, cfg)
    loss_tol, norm_tol = cell.spec["loss_rel_tol"], args.grad_norm_tol
    record = {}

    def report(tag, loss_ref, norms_ref, must_pass):
        rel = {g: [abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(got[g], norms_ref[g])] for g in got}
        worst = max(max(v) for v in rel.values())
        loss_err = abs(got_loss - loss_ref) / abs(loss_ref)
        passes = loss_err <= loss_tol and worst <= norm_tol
        line = {"loss": got_loss, "reference": loss_ref,
                "loss_rel_err": loss_err, "loss_tol": loss_tol,
                "grad_norm_rel_err": rel, "worst_grad_norm": worst,
                "grad_norm_tol": norm_tol, "passes": passes,
                "fails_by": [k for k, bad in (
                    ("loss", loss_err > loss_tol),
                    ("grad_norm", worst > norm_tol)) if bad]}
        record[tag] = line
        print(tag + ": " + json.dumps(line), flush=True)
        return passes == must_pass

    verdict = report("clean", ref_loss, ref_norms, True)
    print("grad_norms: " + json.dumps({"program": got,
                                       "reference": ref_norms}), flush=True)
    if args.update:
        moved = adapter.group_norms(
            {n: jnp.asarray(scope.get(n)) - jnp.asarray(before[n])
             for n in names}, cfg)
        rel = {g: [abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(moved[g], ref_update[g])] for g in moved}
        worst = max(max(v) for v in rel.values())
        record["update"] = {"program": moved, "reference": ref_update,
                            "rel_err": rel, "worst": worst,
                            "tol": args.update_norm_tol,
                            "passes": worst <= args.update_norm_tol}
        print("update: " + json.dumps(record["update"]), flush=True)
        verdict &= record["update"]["passes"]
    # the weights the step started from, for the readings that follow
    params = {n: jnp.asarray(before[n]) for n in names} \
        if args.control or args.faults else None

    def again(tag, ctx):
        with ctx as below:
            t = time.perf_counter()
            loss_b, grads_b = adapter.reference_grads(below, params, batch)
            norms_b = adapter.group_norms(grads_b, cfg)
            del grads_b
            print(f"{tag}: {time.perf_counter() - t:.1f} s", flush=True)
        return report(tag, loss_b, norms_b, False)

    if args.control:
        verdict &= again("control", adapter.one_precision_below(cfg))
    for fault in filter(None, args.faults.split(",")):
        verdict &= again("fault:" + fault, adapter.planted(fault, cfg))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(f"{args.out}_{args.seed}.json", "w") as f:
        json.dump(record, f)
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
