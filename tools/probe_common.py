"""Shared harness for the step-attribution probes (probe_lstm/probe_nmt).

The analytic models that used to live here — the HLO byte parser, the
collective ring wire model, the per-op flop/byte roofline — were promoted
to `paddle_tpu/framework/costs.py` (r12): the framework owns ONE copy the
pipeline partitioner, the cost ledger, and the planner can all query.
This module re-exports them under their historical names so every probe,
bench, and census test keeps importing from one place, and keeps the
measurement-side boilerplate (build -> compile -> cost_analysis ->
best-of-N timing) that only makes sense in the tools tree.
"""
from __future__ import annotations

import time
from typing import Callable, Dict

import numpy as np

from paddle_tpu.framework.costs import (  # noqa: F401
    HLO_ITEM_BYTES, V5E_HBM_BPS, V5E_PEAK_TFLOPS, census_wire_bytes,
    collective_census, collective_wire_bytes, hlo_shape_bytes,
    op_cost_flops_bytes, op_time_cost, program_flops_bytes, roofline_fields)


def measure_step(build: Callable, make_feed: Callable[[], Dict],
                 iters: int = 15, windows: int = 3, hlo_path: str = None):
    """build() -> (loss_var, optimizer); make_feed() -> feed dict.

    Returns {step_s, flops, bytes_acc} with flops/bytes from XLA's own
    cost model for the compiled train step (0.0 when the backend does not
    report them) and step_s the best-of-`windows` mean over `iters` steps,
    host-value realization as the only trusted barrier (see bench.py).
    """
    import jax.numpy as jnp
    import paddle_tpu as pt

    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss, opt = build()
        opt.minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = {k: jnp.asarray(v) for k, v in make_feed().items()}

    prog, scope = pt.default_main_program(), pt.global_scope()
    compiled = exe._lookup_or_compile(prog, feed, [loss.name], scope)
    feed_vals = tuple(jnp.asarray(feed[n]) for n in compiled.feed_names)
    ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
    rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
    ex = compiled.fn.lower(feed_vals, ro_vals, rw_vals,
                           np.uint32(0)).compile()
    if hlo_path:
        with open(hlo_path, "w") as f:
            f.write(ex.as_text())
    ca = ex.cost_analysis() or {}
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    flops = float(ca.get("flops", 0.0))

    o = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    float(np.asarray(o[0]).ravel()[0])  # compile + drain
    best = None
    for _ in range(windows):
        t0 = time.time()
        fetched = []
        for _ in range(iters):
            o = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
            fetched.append(o[0])
        float(np.asarray(fetched[-1]).ravel()[0])
        dt = (time.time() - t0) / iters
        best = dt if best is None else min(best, dt)
    return {"step_s": best, "flops": flops, "bytes_acc": bytes_acc}
