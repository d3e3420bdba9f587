"""Training cells: a new seeded batch every step through Executor.run (or
ParallelExecutor.run on a mesh), the loss fetched to the host every step, as
the program's users call it.

The window holds whole steps only. It ends at the first step boundary at or
after --seconds, and the rate is counted over the steps' own elapsed time,
from the first step's start to the last one's loss on the host. With
--trace 1 the same steps go on under the profiler for the cell's
`trace_seconds` AFTER the window, so the spans and counters of the window are
those of an undisturbed run.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from .. import harness, traffic

WARM_STEPS = 3      # the first compiles or loads; two more settle the allocator


def run(cell, args, t0):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.observability import tracing

    t = time.perf_counter()
    parts = {"import": t - t0}      # interpreter, jax, paddle_tpu, manifest
    device = harness.device_facts(cell.chips)
    compiles = harness.CompileCounter()
    out = harness.Run(cell, args.seed, args.seconds, device)
    mix, cfg, adapter = cell.traffic, cell.config, cell.adapter
    with pt.core.unique_name.guard():
        loss = adapter.build_train(cfg, mix)
        opt = mix["optimizer"]
        if opt["type"] != "adam":
            raise SystemExit(f"unknown optimizer {opt['type']!r}")
        pt.optimizer.AdamOptimizer(
            learning_rate=opt["learning_rate"]).minimize(loss)
    out.batches = traffic.train_batches(mix, args.seed, cell.chips,
                                        adapter.vocabs(cfg))
    parts["build"] = time.perf_counter() - t

    t = time.perf_counter()
    pt.default_startup_program().random_seed = args.seed % (2 ** 31 - 1) + 1
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    if cell.spec["executor"] == "ParallelExecutor":
        from paddle_tpu.parallel import DeviceMesh, ParallelExecutor
        mesh = DeviceMesh(device["devices"], dict(cell.spec["mesh"]))
        exe = ParallelExecutor(loss_name=loss.name, mesh=mesh)

        def step(feed):
            return exe.run(fetch_list=[loss], feed=feed)[0]
    elif cell.spec["executor"] == "Executor":
        def step(feed):
            return exe.run(feed=feed, fetch_list=[loss])[0]
    else:
        raise SystemExit(f"unknown executor {cell.spec['executor']!r}")
    jax.block_until_ready(pt.global_scope().get(adapter.param_names(cfg)[0]))
    parts["init"] = time.perf_counter() - t

    t = time.perf_counter()
    ring = out.batches
    losses = [float(step(ring[0]["feed"]))]
    parts["compile_or_load"] = time.perf_counter() - t
    t = time.perf_counter()
    losses += [float(step(ring[i % len(ring)]["feed"]))
               for i in range(1, WARM_STEPS)]
    parts["warm"] = time.perf_counter() - t

    gc.collect()
    gc.freeze()
    mark = tracing.mark()
    compiled_before = compiles.n
    steps = out.steps
    i = WARM_STEPS
    t_open = time.perf_counter()
    out.setup_s = t_open - t0
    while True:
        batch = ring[i % len(ring)]
        with tracing.span("user", "benchmark/step"):
            ts = time.perf_counter()
            value = step(batch["feed"])
            te = time.perf_counter()
        steps.append((ts, te, batch["tokens"]))
        losses.append(float(value))
        i += 1
        if te - t_open >= args.seconds:
            break
    out.compiles_in_window = compiles.n - compiled_before
    out.spans = tracing.spans_since(mark)
    if args.trace:
        # the traced phase: more of the same steps, after the window
        with harness.Profiler() as profiler:
            t_trace = time.perf_counter()
            while time.perf_counter() - t_trace < cell.spec["trace_seconds"]:
                with tracing.span("user", "benchmark/step"):
                    losses.append(float(step(ring[i % len(ring)]["feed"])))
                i += 1
        out.trace = profiler.result()
    gc.unfreeze()
    out.setup_parts = {k: round(v, 3) for k, v in parts.items()}
    out.attempted = len(steps)
    out.failed = sum(not np.isfinite(x) for x in losses)

    # correctness, outside the window: the loss the system's next step
    # reports on a batch equals the reference's on the weights it starts from
    scope = pt.global_scope()
    params = {n: scope.get(n) for n in adapter.param_names(cfg)}
    check = ring[i % len(ring)]
    ref = adapter.reference_loss(cfg, params, check)
    got = float(step(check["feed"]))
    tol = cell.spec["loss_rel_tol"]
    out.notes = {"loss_first": losses[0], "loss_last": losses[-1],
                 "check_loss": got, "check_reference": ref,
                 "check_rel_err": abs(got - ref) / abs(ref), "check_tol": tol,
                 "steps": len(steps),
                 "step_ms_p50": harness.quantile(
                     [1e3 * (e - s) for s, e, _ in steps], 0.5)}
    out.correct = (out.failed == 0
                   and abs(got - ref) <= tol * abs(ref))
    return out
