"""Training cells: a new seeded batch every step through Executor.run (or
ParallelExecutor.run on a mesh), every step's loss read on the host, the
cell's `steps_ahead` steps late (30: 3.3 to 4.5 seconds of steps).

Steps are dispatched ahead of the one whose loss is waited for
(`return_numpy=False` leaves the loss on the device), so the chip stays fed
while the machine's host stands still for a tenth of a second or for some
seconds: with the loss fetched at every step such a stall was lost step time,
and one run in a few read 2 to 4% low (PERF.md, section 6, PR 35). The
window holds whole steps only. Once --seconds are up nothing more is sent,
every step sent is waited for, and the clock is read after that wait: the
rate counts all of that work over all of that time, from the first step's
dispatch to the last one's loss on the host. With
--trace 1 the same steps go on under the profiler for the cell's
`trace_seconds` AFTER the window, so the spans and counters of the window are
those of an undisturbed run. Set-up is counted from the process's start to
the window's opening less `runtime_start`, the first touch of the device
(metrics/setup_s.py).
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from .. import harness, traffic

WARM_STEPS = 3      # the first compiles or loads; two more settle the allocator


class Pipeline:
    """Steps in flight, oldest first. `send` dispatches one and, once `ahead`
    are in flight, waits for the oldest one's loss; `drain` waits for all."""

    def __init__(self, launch, ahead, tracing):
        self.launch, self.ahead, self.tracing = launch, ahead, tracing
        self.flying = collections.deque()   # (dispatched at, loss, tokens)
        self.steps, self.losses = [], []    # (dispatched, loss here, tokens)

    def send(self, batch):
        with self.tracing.span("user", "benchmark/step"):
            self.flying.append((time.perf_counter(),
                                self.launch(batch["feed"]), batch["tokens"]))
            if len(self.flying) > self.ahead:
                self.land()

    def land(self):
        start, value, tokens = self.flying.popleft()
        with self.tracing.span("user", "benchmark/loss_wait"):
            self.losses.append(float(np.asarray(value)))
        self.steps.append((start, time.perf_counter(), tokens))

    def drain(self):
        while self.flying:
            self.land()


def run(cell, args, t0):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.observability import tracing

    out = harness.start_run(cell, args, t0)
    parts, device = out.setup_parts, out.device
    t = time.perf_counter()
    compiles = harness.CompileCounter()
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

        def step(feed, return_numpy=True):
            return exe.run(fetch_list=[loss], feed=feed,
                           return_numpy=return_numpy)[0]
    elif cell.spec["executor"] == "Executor":
        def step(feed, return_numpy=True):
            return exe.run(feed=feed, fetch_list=[loss],
                           return_numpy=return_numpy)[0]
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
    ahead = cell.spec["steps_ahead"]

    def launch(feed):
        return step(feed, return_numpy=False)

    gc.collect()
    gc.freeze()
    mark = tracing.mark()
    compiled_before = compiles.n
    window = Pipeline(launch, ahead, tracing)
    i = WARM_STEPS
    t_open = time.perf_counter()
    out.open_window(t_open)
    while time.perf_counter() - t_open < args.seconds:
        window.send(ring[i % len(ring)])
        i += 1
    window.drain()
    out.steps, steps = window.steps, window.steps
    losses += window.losses
    out.compiles_in_window = compiles.n - compiled_before
    out.spans = tracing.spans_since(mark)
    if args.trace:
        # the traced phase: more of the same steps, after the window
        traced = Pipeline(launch, ahead, tracing)
        with harness.Profiler() as profiler:
            t_trace = time.perf_counter()
            while time.perf_counter() - t_trace < cell.spec["trace_seconds"]:
                traced.send(ring[i % len(ring)])
                i += 1
            traced.drain()
        losses += traced.losses
        out.trace = profiler.result()
    gc.unfreeze()
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
    gaps = [1e3 * (b[1] - a[1]) for a, b in zip(steps, steps[1:])]
    out.notes = {"loss_first": losses[0], "loss_last": losses[-1],
                 "check_loss": got, "check_reference": ref,
                 "check_rel_err": abs(got - ref) / abs(ref), "check_tol": tol,
                 "steps": len(steps), "steps_ahead": ahead,
                 "window_s": steps[-1][1] - steps[0][0],
                 # from one step's loss on the host to the next one's: the
                 # median, and the longest with the step it came before (a
                 # run that reads low shows here where it stood still)
                 "step_ms_p50": harness.quantile(gaps, 0.5),
                 "step_gap_max_ms": max(gaps),
                 "step_gap_max_at": gaps.index(max(gaps)) + 1}
    out.checks["loss_rel_err"] = (abs(got - ref) / abs(ref), tol)
    out.correct = (out.failed == 0
                   and abs(got - ref) <= tol * abs(ref))
    return out
