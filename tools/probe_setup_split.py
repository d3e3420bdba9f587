"""Probe: where a training cell's set-up goes, up to its first step.

    python3 tools/probe_setup_split.py --workload lm-big_train_1chip [--attention xla]
    python3 tools/probe_setup_split.py --kernels 12 --shape 8,16,1024,64 --causal 1

The first form builds the cell's program as `benchmark/loops/train.py` does,
runs the startup program and ONE step, and prints one JSON line: the loop's
own `build`, `init` and `compile_or_load`, and `spans`, every `compile` span
the program kept (`tracing.compile_spans()`, docs/observability.md): inside
`compile_or_load` lies the `executor/compile_or_load` span whose `program` is
`train_step`, with JAX's seconds of the step's trace, its lowering, the
backend's compile (~0 in a run that loads the executable from the compile
cache) and the cache's retrieval, and the kernels' calls and traced bodies;
`by_kind` has the sums over the process; `stored` says what the executor's
store of ready executables (`paddle_tpu/core/compile_cache.py`) did for the
programs: `hit_share`, the percentage of first runs that found their
executable there and so traced and lowered nothing, and `load_s`, the seconds
each program's executable took to load (a miss's are the retrieval from
JAX's cache, and its `write_s` what the entry cost to write).
`--attention xla` pins every `fused_attention` op to the composite, as
`chip_smoke.py` does: the difference between the two runs is the flash calls'
share. The second form times `jax.jit(jax.grad(...)).trace()` and `.lower()`
of N flash calls alone, head-major as `flash_attention` takes them.

A run that finds the cache cold fills it: run twice and read the second.
Run from the root of the tree to be probed (PR 47, Step 0)."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KINDS = ("trace_s", "lower_s", "compile_s", "cache_load_s", "executables")


def told(span):
    """A kept `compile` span as the line shows it."""
    return {"name": span.name, "s": round(span.end - span.start, 3),
            **{k: round(v, 3) if isinstance(v, float) else v
               for k, v in span.attrs.items()}}


def stored(kept):
    """What the store did for the first runs among the kept spans."""
    runs = [s for s in kept if s.name == "executor/compile_or_load"]
    keyed = [s for s in runs if "stored" in s.attrs]
    return {
        "programs": len(runs), "keyed": len(keyed),
        "hit_share": round(100.0 * sum(s.attrs["stored"] for s in keyed)
                           / len(keyed), 1) if keyed else None,
        "load_s": {s.attrs["program"]: round(s.attrs["cache_load_s"], 3)
                   for s in runs},
        "write_s": {s.attrs["program"]: round(s.attrs["store_write_s"], 3)
                    for s in runs if "store_write_s" in s.attrs}}


def probe_cell(args):
    t0 = time.perf_counter()
    import jax
    import paddle_tpu as pt
    from benchmark import harness, traffic
    from paddle_tpu.observability import tracing

    cell = harness.Cell(args.workload)
    parts = {"import": time.perf_counter() - t0}
    t = time.perf_counter()
    device = harness.device_facts(cell.chips)
    parts["runtime_start"] = time.perf_counter() - t

    t = time.perf_counter()
    mix, cfg, adapter = cell.traffic, cell.config, cell.adapter
    with pt.core.unique_name.guard():
        loss = adapter.build_train(cfg, mix)
        pt.optimizer.AdamOptimizer(
            learning_rate=mix["optimizer"]["learning_rate"]).minimize(loss)
    n_attention = 0
    for op in pt.default_main_program().global_block().ops:
        if op.type == "fused_attention":
            n_attention += 1
            if args.attention:
                op.attrs["backend"] = args.attention
    batches = traffic.train_batches(mix, args.seed, cell.chips,
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
    else:
        def step(feed):
            return exe.run(feed=feed, fetch_list=[loss])[0]
    jax.block_until_ready(pt.global_scope().get(adapter.param_names(cfg)[0]))
    parts["init"] = time.perf_counter() - t

    t = time.perf_counter()
    first = float(step(batches[0]["feed"]))
    parts["compile_or_load"] = time.perf_counter() - t
    t = time.perf_counter()
    second = float(step(batches[1 % len(batches)]["feed"]))
    parts["second_step"] = time.perf_counter() - t
    kept = tracing.compile_spans()
    print(json.dumps({
        "workload": args.workload, "attention": args.attention or "default",
        "fused_attention_ops": n_attention,
        "parts": {k: round(v, 3) for k, v in parts.items()},
        "by_kind": {k: round(sum(s.attrs.get(k, 0) for s in kept), 3)
                    for k in KINDS},
        "stored": stored(kept),
        "spans": [told(s) for s in kept],
        "loss": [first, second],
        "device": f"{device['platform']} {device['kind']} x{device['count']}",
    }), flush=True)


def probe_kernels(args):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    shape = tuple(int(x) for x in args.shape.split(","))
    scale = shape[-1] ** -0.5

    def loss(q, k, v):
        x = q
        for _ in range(args.kernels):
            x = pk.flash_attention(x, k, v, scale=scale,
                                   causal=bool(args.causal),
                                   backend="pallas")
        return jnp.sum(x.astype(jnp.float32))

    s = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    out = {"kernels": args.kernels, "shape": list(shape),
           "causal": bool(args.causal), "rounds": []}
    for _ in range(2):      # the second round has every import behind it
        jax.clear_caches()
        t = time.perf_counter()
        traced = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(s, s, s)
        t1 = time.perf_counter()
        lowered = traced.lower(lowering_platforms=("tpu",))
        t2 = time.perf_counter()
        text = lowered.as_text()
        out["rounds"].append({"trace_s": round(t1 - t, 3),
                              "lower_s": round(t2 - t1, 3),
                              "custom_calls": text.count("tpu_custom_call"),
                              "module_mb": round(len(text) / 1e6, 3)})
    print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload")
    ap.add_argument("--attention", default=None)
    ap.add_argument("--seed", type=int, default=3000000001)
    ap.add_argument("--kernels", type=int, default=0)
    ap.add_argument("--shape", default="8,16,1024,64")
    ap.add_argument("--causal", type=int, default=1)
    args = ap.parse_args()
    if args.kernels:
        probe_kernels(args)
    else:
        probe_cell(args)


if __name__ == "__main__":
    main()
