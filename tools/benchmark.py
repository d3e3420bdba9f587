#!/usr/bin/env python
"""Benchmark CLI over the model zoo.

≙ reference benchmark/fluid/fluid_benchmark.py (models mnist / resnet / vgg /
stacked_dynamic_lstm / machine_translation with --update_method
{local,pserver,nccl2}, printing images/sec). TPU translation: the pserver and
nccl2 modes collapse into `--update_method collective` (ParallelExecutor over
the device mesh — compiled XLA collectives); `local` is the single-device
Executor; `multiproc` launches a REAL N-process jax.distributed world
(≙ the nccl2 multi-trainer path, fluid_benchmark.py:30-61) on this host's
virtual CPU mesh and reports per-process step time vs the single-process
collective baseline (the process-boundary overhead). Synthetic data keeps
the harness runnable anywhere (≙ --use_fake_data).

Examples:
    python tools/benchmark.py --model resnet --batch_size 64 --iters 20
    python tools/benchmark.py --model transformer --update_method collective
    python tools/benchmark.py --model mnist --update_method multiproc \
        --nproc 4 --local_devices 2 --iters 10
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import socket
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _mnist(args, rng):
    from paddle_tpu import layers
    from paddle_tpu.models import mnist
    loss, acc = mnist.mlp()[:2]
    feed = {"img": rng.rand(args.batch_size, 784).astype("float32"),
            "label": rng.randint(0, 10,
                                 (args.batch_size, 1)).astype("int64")}
    return loss, feed, args.batch_size


def _resnet(args, rng):
    from paddle_tpu.models import resnet
    loss, acc, _ = resnet.resnet_imagenet(
        depth=args.depth, data_format="NHWC", use_bf16=not args.no_bf16,
        class_num=1000)
    feed = {"img": rng.rand(args.batch_size, 224, 224, 3).astype("float32"),
            "label": rng.randint(0, 1000,
                                 (args.batch_size, 1)).astype("int64")}
    return loss, feed, args.batch_size


def _vgg(args, rng):
    from paddle_tpu.models import vgg
    loss, acc, _ = vgg.vgg(depth=16, class_num=1000,
                           image_shape=[224, 224, 3],
                           data_format="NHWC", use_bf16=not args.no_bf16)
    feed = {"img": rng.rand(args.batch_size, 224, 224, 3).astype("float32"),
            "label": rng.randint(0, 1000,
                                 (args.batch_size, 1)).astype("int64")}
    return loss, feed, args.batch_size


def _se_resnext(args, rng):
    from paddle_tpu import layers
    from paddle_tpu.models import se_resnext
    loss, acc, _ = se_resnext.se_resnext_imagenet(
        depth=50, use_bf16=not args.no_bf16)
    feed = {"img": rng.rand(args.batch_size, 224, 224, 3).astype("float32"),
            "label": rng.randint(0, 1000,
                                 (args.batch_size, 1)).astype("int64")}
    return loss, feed, args.batch_size


def _googlenet(args, rng):
    from paddle_tpu.models import googlenet
    loss, acc, _ = googlenet.googlenet_imagenet(use_bf16=not args.no_bf16)
    feed = {"img": rng.rand(args.batch_size, 224, 224, 3).astype("float32"),
            "label": rng.randint(0, 1000,
                                 (args.batch_size, 1)).astype("int64")}
    return loss, feed, args.batch_size


def _stacked_lstm(args, rng):
    import numpy as np
    from paddle_tpu.models import stacked_lstm
    seq = args.seq_len
    loss, acc, _ = stacked_lstm.stacked_lstm_net(
        dict_dim=10000, emb_dim=256, hid_dim=256, max_len=seq)
    feed = {"words": rng.randint(0, 10000,
                                 (args.batch_size, seq)).astype("int64"),
            "words@SEQLEN": np.full((args.batch_size,), seq, dtype="int32"),
            "label": rng.randint(0, 2,
                                 (args.batch_size, 1)).astype("int64")}
    return loss, feed, args.batch_size


def _machine_translation(args, rng):
    from paddle_tpu import layers
    from paddle_tpu.models import machine_translation as mt
    import numpy as np
    Ts = Tt = args.seq_len
    V = 10000
    src = layers.data("src", shape=[Ts], dtype="int64")
    src_lens = layers.data("src_lens", shape=[], dtype="int64")
    tgt_in = layers.data("tgt_in", shape=[Tt], dtype="int64")
    tgt_out = layers.data("tgt_out", shape=[Tt], dtype="int64")
    tgt_mask = layers.data("tgt_mask", shape=[Tt], dtype="float32")
    loss, _ = mt.train_net(src, src_lens, tgt_in, tgt_out, tgt_mask,
                           dict_size=V, embed_dim=256, hidden_dim=512)
    b = args.batch_size
    feed = {"src": rng.randint(2, V, (b, Ts)).astype("int64"),
            "src_lens": np.full((b,), Ts, "int64"),
            "tgt_in": rng.randint(2, V, (b, Tt)).astype("int64"),
            "tgt_out": rng.randint(2, V, (b, Tt)).astype("int64"),
            "tgt_mask": np.ones((b, Tt), "float32")}
    return loss, feed, b * Tt  # tokens/sec


def _transformer(args, rng):
    from paddle_tpu.models import transformer
    import numpy as np
    T = args.seq_len
    # mean_loss: identical math for the full-length feed below, and the
    # MEAN reduction form both manual modes (reduce_scatter, tp) require
    loss, _ = transformer.transformer_lm(
        vocab=32000, max_len=T, d_model=512, d_inner=2048, num_heads=8,
        num_layers=6, dropout=0.0, mean_loss=True)
    b = args.batch_size
    feed = {"tokens": rng.randint(0, 32000, (b, T)).astype("int64"),
            "tokens@SEQLEN": np.full((b,), T, "int32"),
            "targets": rng.randint(0, 32000, (b, T)).astype("int64")}
    return loss, feed, b * T  # tokens/sec


def _deepfm(args, rng):
    from paddle_tpu.models import deepfm
    import numpy as np
    b = args.batch_size
    loss, _ = deepfm.deepfm(num_fields=39, vocab_size=100000)
    feed = {"feat_ids": rng.randint(0, 100000, (b, 39)).astype("int64"),
            "feat_vals": rng.rand(b, 39).astype("float32"),
            "label": rng.randint(0, 2, (b, 1)).astype("float32")}
    return loss, feed, b


MODELS = {
    "mnist": _mnist,
    "resnet": _resnet,
    "vgg": _vgg,
    "se_resnext": _se_resnext,
    "googlenet": _googlenet,
    "stacked_lstm": _stacked_lstm,
    "machine_translation": _machine_translation,
    "transformer": _transformer,
    "deepfm": _deepfm,
}


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_child(args, extra_env, extra_args=()):
    """Re-exec this CLI as a child process on the virtual CPU platform.
    Output goes to temp FILES, not pipes: the parent polls without
    draining, and a pipe-buffered child (~64 KB of XLA/absl log spew)
    would deadlock in write() and read as a hang."""
    import tempfile
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PADDLE_")}   # no stale world config leaks
    # the children share this host with the parent and must never ask for
    # the chip: pin them to the CPU through their own environment
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env)
    argv = [sys.executable, os.path.abspath(__file__),
            "--model", args.model, "--batch_size", str(args.batch_size),
            "--iters", str(args.iters), "--warmup", str(args.warmup),
            "--seq_len", str(args.seq_len), "--depth", str(args.depth),
            "--learning_rate", str(args.learning_rate),
            "--optimizer", args.optimizer,
            "--reduce_mode", args.reduce_mode,
            "--comm_bucket_bytes", str(args.comm_bucket_bytes),
            "--pipeline_stages", str(args.pipeline_stages),
            "--num_microbatches", str(args.num_microbatches),
            "--pipeline_schedule", args.pipeline_schedule] \
        + list(extra_args)
    if args.no_bf16:
        argv.append("--no_bf16")
    if args.comm_error_feedback:
        argv.append("--comm_error_feedback")
    if args.no_census:
        argv.append("--no_census")
    out_f = tempfile.TemporaryFile(mode="w+", prefix="ptpu_bench_out_")
    err_f = tempfile.TemporaryFile(mode="w+", prefix="ptpu_bench_err_")
    p = subprocess.Popen(argv, stdout=out_f, stderr=err_f, text=True,
                         env=env)
    p._ptpu_out, p._ptpu_err = out_f, err_f
    return p


def _child_output(p):
    out = err = ""
    for attr, var in (("_ptpu_out", "out"), ("_ptpu_err", "err")):
        f = getattr(p, attr, None)
        if f is not None:
            f.seek(0)
            text = f.read()
            f.close()
            if var == "out":
                out = text
            else:
                err = text
    return out, err


def _drive_quant_serving(args):
    """--quant_params: the weight-only quantized serving column family.

    Runs the continuous-batching decode engine twice on ONE weight set —
    f32 baseline, then quantized (framework/passes.py
    quantize_params_pass) — and prints one row per side with
    params_bytes before/after, the per-tick host-dispatch share from the
    engine's `ptpu_engine_dispatch_seconds` histogram (the zero-dispatch
    bound-tick path), and generated tokens/s. Greedy argmax on shared
    weights, so the token streams are also compared (int8 is typically
    token-identical; divergence is reported, not asserted — the serving
    tests pin the bound)."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.serving import ContinuousBatchingEngine

    dims = dict(vocab=1000, max_len=64, d_model=64, d_inner=128,
                num_heads=4, num_layers=2)
    n_slots = max(2, min(args.batch_size, 8))
    pt.reset_default_programs()
    pt.reset_global_scope()
    scope = pt.global_scope()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, dims["vocab"], 4).tolist()
               for _ in range(4 * n_slots)]
    rows, tokens = [], {}
    for quant in (None, args.quant_params):
        label = quant or "f32"
        eng = ContinuousBatchingEngine(n_slots=n_slots, scope=scope,
                                       cache_prefix=f"bq_{label}",
                                       quant=quant, **dims)
        warm = eng.submit([1], max_new=1)
        eng.run_until_idle()
        assert warm.done
        t0 = time.time()
        reqs = [eng.submit(list(p), max_new=16) for p in prompts]
        eng.run_until_idle()
        dt = time.time() - t0
        n_tok = sum(len(r.tokens) for r in reqs)
        tokens[label] = [r.tokens for r in reqs]
        rows.append({
            "engine": label,
            "params_bytes": (eng.params_bytes_quantized if eng.quant
                             else eng.params_bytes_f32),
            "quant_freed_bytes": eng.quant_freed_bytes,
            "dispatch_ms_p50": round(
                (eng._m_dispatch.quantile(0.5) or 0.0) * 1e3, 4),
            "tick_ms_p50": round(
                (eng._m_tick_latency.quantile(0.5) or 0.0) * 1e3, 4),
            "tokens_per_sec": round(n_tok / dt, 1),
        })
    import jax
    print(json.dumps({
        "model": "transformer_serving",
        "quant_params": args.quant_params,
        "batch_slots": n_slots,
        "params_bytes_before": rows[0]["params_bytes"],
        "params_bytes_after": rows[1]["params_bytes"],
        "params_ratio": round(rows[0]["params_bytes"]
                              / max(rows[1]["params_bytes"], 1), 3),
        "decode_token_identical": tokens["f32"]
            == tokens[args.quant_params],
        "rows": rows,
        "device": jax.devices()[0].platform,
    }))


def _drive_offload_serving(args):
    """--offload: the two-tier host-offload serving column family.

    Runs the paged decode engine twice at a deliberately tight device
    block pool on ONE weight set — device-only (head-of-line admission)
    vs two-tier (framework/offload.py host spill + prefetch) — and
    prints one row per side with admitted concurrency under backlog,
    tokens/s, the offload wire-byte columns, and the prefetch hit rate.
    Decode must stay token-identical across the pair and the wire
    census must reconcile EXACTLY (predicted = eviction/reload counters
    x per-block bytes vs the transfer stream's measured bytes) — both
    are asserted, same discipline as BENCH_OFFLOAD_r23.json."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.framework import offload as _offload
    from paddle_tpu.serving import HostTierConfig, PagedKVEngine

    dims = dict(vocab=1000, max_len=64, d_model=64, d_inner=128,
                num_heads=4, num_layers=2)
    n_slots = max(2, min(args.batch_size, 16))
    pt.reset_default_programs()
    pt.reset_global_scope()
    scope = pt.global_scope()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, dims["vocab"], 4).tolist()
               for _ in range(3 * n_slots)]
    tier = HostTierConfig(host_blocks=64, prefetch_distance=2,
                          rotate_quantum=8)
    rows, tokens = [], {}
    for label, host_tier in (("device_only", None), ("two_tier", tier)):
        _offload.reset_offload()
        eng = PagedKVEngine(n_slots=n_slots, block_size=8, n_blocks=13,
                            scope=scope, cache_prefix=f"bo_{label}",
                            host_tier=host_tier, **dims)
        warm = eng.submit([1], max_new=1)
        eng.run_until_idle()
        assert warm.done
        eng.ht_d2h_bytes = eng.ht_h2d_bytes = 0
        eng.pager.host_evictions = eng.pager.host_reloads = 0
        eng.pager.host_prefetch_hits = eng.pager.host_prefetch_misses = 0
        t0 = time.time()
        reqs = [eng.submit(list(p), max_new=16) for p in prompts]
        active = []
        while eng.n_active or eng.n_pending:
            backlogged = eng.n_pending > 0
            eng.step()
            if backlogged and eng.n_active:
                active.append(eng.n_active)
        dt = time.time() - t0
        tokens[label] = [list(r.tokens) for r in reqs]
        ht = eng.pager.stats()["host_tier"]
        per = eng._ht_per_block_bytes
        census_exact = True
        if host_tier is not None:
            eng.pager.check_two_tier()
            census_exact = (
                eng.ht_d2h_bytes == ht["host_evictions"] * per
                and eng.ht_h2d_bytes == ht["host_reloads"] * per)
        rows.append({
            "engine": label,
            "admitted_concurrency": round(
                float(np.mean(active)) if active else 0.0, 2),
            "tokens_per_sec": round(
                sum(len(r.tokens) for r in reqs) / dt, 1),
            "offload_d2h_bytes": int(eng.ht_d2h_bytes),
            "offload_h2d_bytes": int(eng.ht_h2d_bytes),
            "prefetch_hit_rate": round(
                ht["prefetch_hit_rate"], 3) if ht else 0.0,
            "census_exact": bool(census_exact),
        })
    identical = tokens["device_only"] == tokens["two_tier"]
    import jax
    print(json.dumps({
        "model": "transformer_serving_paged",
        "offload": True,
        "batch_slots": n_slots,
        "n_blocks": 13,
        "host_tier": {"host_blocks": tier.host_blocks,
                      "prefetch_distance": tier.prefetch_distance,
                      "rotate_quantum": tier.rotate_quantum},
        "decode_token_identical": bool(identical),
        "rows": rows,
        "device": jax.devices()[0].platform,
    }))
    assert identical, "two-tier decode diverged from device-only"
    assert all(r["census_exact"] for r in rows), \
        "offload wire census did not reconcile"


def _drive_multiproc(args):
    """Parent of the N-process world: spawn N trainer children + a
    1-process collective baseline on the same total device count, report
    the process-boundary overhead (≙ fluid_benchmark.py nccl2 launcher)."""
    total_dev = args.nproc * args.local_devices
    port = _free_port()
    trace_dir = args.trace_dir
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
    procs = []
    for rank in range(args.nproc):
        extra = {
            "PADDLE_TRAINING_ROLE": "TRAINER",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(args.nproc),
            "PADDLE_COORDINATOR_ENDPOINT": f"127.0.0.1:{port}",
            "XLA_FLAGS":
                f"--xla_force_host_platform_device_count="
                f"{args.local_devices}",
        }
        worker_args = ["--update_method", "collective"]
        if trace_dir:
            worker_args += ["--trace_dir", trace_dir]
        procs.append(_spawn_child(args, extra, worker_args))
    ranks = {}
    try:
        # poll ALL ranks: a crashed rank must surface ITS stderr
        # immediately, not after a sibling's 900 s collective hang
        deadline = time.time() + 900
        pending = list(procs)
        while pending:
            for p in list(pending):
                if p.poll() is not None:
                    out, err = _child_output(p)
                    if p.returncode != 0:
                        raise RuntimeError(
                            f"worker failed (rc={p.returncode}):\n"
                            f"{err[-3000:]}")
                    rec = json.loads(out.strip().splitlines()[-1])
                    ranks[rec.get("rank", 0)] = rec
                    pending.remove(p)
            if pending:
                if time.time() > deadline:
                    raise RuntimeError(
                        f"{len(pending)} worker(s) still running at the "
                        f"900 s deadline")
                time.sleep(0.5)
    finally:
        # one failed/hung rank must not orphan siblings blocked in a
        # collective that will never complete
        for p in procs:
            if p.poll() is None:
                p.kill()

    base = _spawn_child(args, {
        "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={total_dev}",
    }, ["--update_method", "collective"])
    try:
        base.wait(timeout=900)
    finally:
        # mirror the worker cleanup: a hung baseline must not stay
        # orphaned past the deadline, and its temp output files must be
        # closed (TemporaryFile unlinks on close) even on the raise path
        if base.poll() is None:
            base.kill()
            base.wait()
            _child_output(base)  # drain + close -> files reclaimed
            raise RuntimeError(
                "single-process baseline still running at the 900 s "
                "deadline; killed")
    out, err = _child_output(base)
    if base.returncode != 0:
        raise RuntimeError(f"baseline failed:\n{err[-3000:]}")
    baseline = json.loads(out.strip().splitlines()[-1])

    worst = max(r["latency_ms"] for r in ranks.values())
    overhead = (worst - baseline["latency_ms"]) / baseline["latency_ms"]
    # a tiny-compute config (mnist: ~10 ms/step) cannot amortize gloo
    # collective latency, and a 3000% "overhead" reads as a measurement
    # when it is a degeneracy (VERDICT r5 weak #5): below the threshold
    # the pct is suppressed and the ABSOLUTE per-step collective cost is
    # reported instead — that number IS interpretable (it is the
    # cross-process collective latency this host pays per step,
    # independent of how little compute hides under it)
    degenerate = baseline["latency_ms"] < 50.0
    collective_cost_ms = round(worst - baseline["latency_ms"], 3)
    merged_trace = None
    if trace_dir:
        import glob

        from paddle_tpu import profiler as prof
        paths = sorted(glob.glob(os.path.join(trace_dir,
                                              "trace_rank*.json")))
        if paths:
            merged_trace = prof.merge_process_traces(
                paths, os.path.join(trace_dir, "merged_trace.json"))
    # the per-rank comm fields are identical across ranks (same compiled
    # step); lift rank 0's into the aggregate row so multiproc rows stay
    # self-interpreting like the collective ones
    rank0 = ranks.get(0, {})
    comm_fields = {k: rank0[k] for k in
                   ("reduce_mode", "grad_bytes_on_wire",
                    "param_allgather_bytes_on_wire", "wire_bytes_per_step",
                    "wire_bytes_census", "census_collectives")
                   if k in rank0}
    print(json.dumps({
        "model": args.model,
        "update_method": "multiproc",
        "nproc": args.nproc,
        "local_devices_per_proc": args.local_devices,
        "total_devices": total_dev,
        "batch_size": args.batch_size,
        **comm_fields,
        "per_process_latency_ms": {str(k): v["latency_ms"]
                                   for k, v in sorted(ranks.items())},
        "worst_rank_latency_ms": worst,
        "single_process_latency_ms": baseline["latency_ms"],
        "multiproc_overhead_pct": (None if degenerate
                                   else round(overhead * 100, 1)),
        "collective_cost_ms_per_step": collective_cost_ms,
        "degenerate": degenerate,
        **({"degenerate_note":
            f"single-process step ({baseline['latency_ms']} ms) is too "
            f"small to amortize cross-process collectives; pct "
            f"suppressed — read collective_cost_ms_per_step "
            f"({collective_cost_ms} ms) as this host's per-step "
            f"collective latency census instead"} if degenerate else {}),
        "throughput": min(r["throughput"] for r in ranks.values()),
        "unit": baseline["unit"],
        "merged_trace": merged_trace,
    }))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", choices=sorted(MODELS), default="resnet")
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--seq_len", type=int, default=64)
    p.add_argument("--depth", type=int, default=50)
    p.add_argument("--learning_rate", type=float, default=0.01)
    p.add_argument("--update_method",
                   choices=["local", "collective", "multiproc"],
                   default="local",
                   help="local = single device; collective = "
                        "ParallelExecutor over the mesh (≙ nccl2/pserver); "
                        "multiproc = N-process jax.distributed world on the "
                        "virtual CPU mesh (≙ nccl2 multi-trainer)")
    p.add_argument("--nproc", type=int, default=4,
                   help="multiproc: number of trainer processes")
    p.add_argument("--local_devices", type=int, default=2,
                   help="multiproc: virtual devices per process")
    p.add_argument("--optimizer", default="momentum",
                   choices=["sgd", "momentum", "adam"])
    p.add_argument("--reduce_mode", default="allreduce",
                   choices=["allreduce", "reduce_scatter", "quantized"],
                   help="gradient path for collective/multiproc runs: "
                        "allreduce = SPMD default; reduce_scatter = "
                        "explicit psum_scatter + sharded update + "
                        "all-gather; quantized = reduce_scatter with "
                        "int8 block-scaled transfers "
                        "(parallel/grad_comm.py)")
    p.add_argument("--comm_error_feedback", action="store_true",
                   help="per-replica error feedback for quantized mode")
    p.add_argument("--comm_bucket_bytes", type=int, default=-1,
                   help="gradient transfer bucket cap; -1 = strategy "
                        "default (4 MiB), 0 = one collective per gradient "
                        "(the probe_overlap A/B side)")
    p.add_argument("--pipeline_stages", type=int, default=0,
                   help="collective runs: pipeline-parallel stages K "
                        "(>= 2 cuts the op DAG over a pp mesh axis of "
                        "size K; the remaining devices form the dp axis). "
                        "0 = off (framework/passes.py "
                        "pipeline_partition_pass)")
    p.add_argument("--num_microbatches", type=int, default=4,
                   help="pipeline runs: microbatches M per step (batch "
                        "must divide by dp * M); bubble fraction is "
                        "(K-1)/(M+K-1)")
    p.add_argument("--pipeline_schedule", default="1f1b",
                   choices=["gpipe", "1f1b"],
                   help="pipeline runs: gpipe (all-fwd then all-bwd) or "
                        "1f1b (bounded activation stash)")
    p.add_argument("--tp", type=int, default=0,
                   help="collective runs: tensor-parallel degree T (>= 2 "
                        "adds a tp mesh axis, annotates the model with "
                        "the Megatron column/row/vocab recipe via "
                        "parallel.auto_shard.annotate_tp, and — in the "
                        "manual reduce_scatter/quantized modes — runs the "
                        "framework/sharding.py tp_shard_pass rewrite). "
                        "Composes with --pipeline_stages on a "
                        "dp x pp x tp mesh")
    p.add_argument("--auto", action="store_true",
                   help="let the auto-parallel planner "
                        "(framework/auto_parallel.py) choose the whole "
                        "strategy — mesh factorization over ALL visible "
                        "devices, reduce mode, quantized wire, buckets, "
                        "pipeline schedule/microbatches, memory plan — "
                        "instead of the flags below; forces "
                        "--update_method collective and emits "
                        "plan_predicted_ms / plan_rank / plan_search_s "
                        "columns. Mutually exclusive with --reduce_mode/"
                        "--pipeline_stages/--tp")
    p.add_argument("--no_census", action="store_true",
                   help="skip the HLO comm census fields (saves one AOT "
                        "compile on big models)")
    p.add_argument("--memory_plan", action="store_true",
                   help="also compile the memory-PLANNED twin "
                        "(framework/memory_plan.py, budget 2%% of the "
                        "measured step) and fill the "
                        "mem_planned_peak_bytes / mem_plan_reduction "
                        "columns from its MEASURED census (one extra "
                        "compile; needs the census, i.e. not "
                        "--no_census)")
    p.add_argument("--quant_params", choices=("int8", "int4"), default=None,
                   help="serving mode: run the continuous-batching decode "
                        "engine f32 vs weight-only-quantized "
                        "(quantize_params_pass) on one weight set and "
                        "print the quantized column family — params_bytes "
                        "before/after, per-tick dispatch_ms (the "
                        "zero-dispatch bound tick's host share), "
                        "tokens/s. Ignores the training flags")
    p.add_argument("--offload", action="store_true",
                   help="serving mode: run the paged decode engine at a "
                        "tight device block pool, device-only vs "
                        "two-tier host offload (framework/offload.py), "
                        "and print the offload column family — admitted "
                        "concurrency under backlog, tokens/s, "
                        "offload_{d2h,h2d}_bytes, prefetch_hit_rate. "
                        "Asserts token identity and the exact wire-byte "
                        "census. Ignores the training flags")
    p.add_argument("--no_bf16", action="store_true")
    p.add_argument("--profile", action="store_true")
    p.add_argument("--trace_dir", default=None,
                   help="write a per-rank Chrome trace here (multiproc "
                        "parent merges them into merged_trace.json)")
    args = p.parse_args()
    if args.iters < 1:
        p.error("--iters must be >= 1")
    if args.warmup < 0:
        p.error("--warmup must be >= 0")
    if args.auto:
        if (args.reduce_mode != "allreduce" or args.pipeline_stages
                or args.tp or args.update_method == "multiproc"):
            p.error("--auto owns the strategy; do not combine it with "
                    "--reduce_mode/--pipeline_stages/--tp/multiproc")
        args.update_method = "collective"

    if args.quant_params:
        _drive_quant_serving(args)
        return

    if args.offload:
        _drive_offload_serving(args)
        return

    if args.update_method == "multiproc":
        _drive_multiproc(args)
        return

    import numpy as np
    import jax
    import paddle_tpu as pt

    if args.no_bf16:
        # also flip the global matmul kill switch: builders that hardcode
        # use_bf16=True (transformer) honor --no_bf16 through it
        from paddle_tpu.core import flags as _flags
        _flags.set_flag("use_bf16_matmul", False)

    from paddle_tpu.distributed import init_parallel_env
    denv = init_parallel_env()  # no-op without PADDLE_COORDINATOR_ENDPOINT

    rng = np.random.RandomState(0)
    loss, feed, units_per_step = MODELS[args.model](args, rng)

    opt = {"sgd": lambda: pt.optimizer.SGDOptimizer(args.learning_rate),
           "momentum": lambda: pt.optimizer.MomentumOptimizer(
               args.learning_rate, momentum=0.9),
           "adam": lambda: pt.optimizer.AdamOptimizer(args.learning_rate),
           }[args.optimizer]()
    opt.minimize(loss)

    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    plan_fields = {}
    if args.auto:
        # planner-chosen strategy over every visible device: annotate tp
        # first (transformer-family models pick up the Megatron recipe;
        # models nothing matches keep tp pruned with a named reason),
        # then search from the default BuildStrategy base
        from paddle_tpu.framework import auto_parallel as _auto
        from paddle_tpu.parallel import ParallelExecutor, annotate_tp
        from paddle_tpu.parallel.mesh import DeviceMesh
        annotate_tp()
        plan_res = _auto.plan(pt.default_main_program(),
                              len(jax.devices()),
                              nominal_batch=args.batch_size)
        runner = ParallelExecutor(
            loss_name=loss.name, build_strategy=plan_res.strategy,
            mesh=DeviceMesh(jax.devices(), plan_res.mesh_axes))
        plan_fields = {
            "auto": True,
            "plan_point": plan_res.point.describe(),
            "plan_mesh_axes": dict(plan_res.mesh_axes),
            "plan_predicted_ms":
                round(plan_res.predicted_step_s * 1e3, 6),
            "plan_rank": plan_res.rank_of(plan_res.point),
            "plan_search_s": round(plan_res.search_s, 3),
            "plan_n_feasible": plan_res.n_feasible,
            "plan_rejections": dict(plan_res.rejections),
        }
    elif args.update_method == "collective":
        from paddle_tpu.parallel import ParallelExecutor
        from paddle_tpu.parallel.strategy import (BuildStrategy,
                                                  ReduceStrategy)
        bst = BuildStrategy()
        bst.reduce_strategy = {
            "allreduce": ReduceStrategy.AllReduce,
            "reduce_scatter": ReduceStrategy.ReduceScatter,
            "quantized": ReduceStrategy.ReduceScatter,
        }[args.reduce_mode]
        if args.reduce_mode == "quantized":
            bst.quant_comm = "int8"
        bst.comm_error_feedback = args.comm_error_feedback
        if args.comm_bucket_bytes >= 0:
            bst.comm_bucket_bytes = args.comm_bucket_bytes
        mesh = None
        t = max(args.tp, 1)
        if args.tp > 1:
            from paddle_tpu.parallel import annotate_tp
            annotated = annotate_tp()
            if not annotated:
                p.error(f"--tp {args.tp}: no parameter of model "
                        f"{args.model!r} matches the annotate_tp rules "
                        f"(transformer-family names)")
        if args.pipeline_stages > 1:
            from paddle_tpu.parallel.mesh import DeviceMesh
            bst.pipeline_stages = args.pipeline_stages
            bst.num_microbatches = args.num_microbatches
            bst.pipeline_schedule = args.pipeline_schedule
            devs = jax.devices()
            k = args.pipeline_stages
            if len(devs) % (k * t):
                p.error(f"--pipeline_stages {k} x --tp {t} must divide "
                        f"the device count {len(devs)}")
            axes = {"dp": len(devs) // (k * t), "pp": k}
            if t > 1:
                axes["tp"] = t
            mesh = DeviceMesh(devs, axes)
        elif t > 1:
            from paddle_tpu.parallel.mesh import DeviceMesh
            devs = jax.devices()
            if len(devs) % t:
                p.error(f"--tp {t} must divide the device count "
                        f"{len(devs)}")
            mesh = DeviceMesh(devs, {"dp": len(devs) // t, "tp": t})
        runner = ParallelExecutor(loss_name=loss.name, build_strategy=bst,
                                  mesh=mesh)
    else:
        runner = exe

    if args.profile:
        pt.profiler.start_profiler("All")
    out = None
    for _ in range(args.warmup):
        out = runner.run(feed=feed, fetch_list=[loss], return_numpy=False)
    if out is not None:
        jax.block_until_ready(out)

    trace_events = args.trace_dir is not None
    if trace_events:
        pt.profiler.reset_profiler()
        pt.profiler.start_profiler("All")
    # the timed window's spans come from the observability tracer — the
    # same executor/engine instrumentation every run records — instead of
    # per-tool perf_counter pairs; span_ms below is the per-step breakdown
    from paddle_tpu.observability import tracing as _tracing
    bench_mark = _tracing.mark()
    t0 = time.time()
    for i in range(args.iters):
        with _tracing.span("user", "bench/step", i=i):
            out = runner.run(feed=feed, fetch_list=[loss],
                             return_numpy=False)
    jax.block_until_ready(out)
    dt = time.time() - t0
    span_agg = _tracing.aggregate(_tracing.spans_since(bench_mark))
    span_ms = {name: round(row["total_ms"] / args.iters, 3)
               for name, row in sorted(span_agg.items())
               if name != "bench/step"}
    if args.profile:
        pt.profiler.stop_profiler(sorted_key="total")
    if trace_events:
        pt.profiler.export_chrome_tracing(os.path.join(
            args.trace_dir, f"trace_rank{denv.trainer_id}.json"))

    comm_fields = {}
    if args.update_method == "collective":
        # self-interpreting comm fields (≙ the r07 breadth rows carrying
        # bound_kind): which gradient path ran and what it puts on the
        # wire per device per step — analytic from the rewritten program's
        # comm plan, cross-checked by the HLO census when affordable
        # (the census == analytic balance is asserted exactly in
        # tests/test_zero_comm.py)
        from paddle_tpu.parallel import grad_comm as _gc
        from paddle_tpu.parallel.strategy import ReduceStrategy as _RS
        prog, scope = pt.default_main_program(), pt.global_scope()
        dp = runner._dp
        rewritten = runner._prepare_program(prog, scope)
        # same model selection as costs.predict: the SPMD ZeRO-1 mode
        # costs the sharded-update param all-gather on top of the grad
        # all-reduce (census-measured) — an allreduce-priced fallback
        # would under-report the --auto rows whenever the planner picks
        # reduce mode
        spmd_model = (_gc.spmd_zero1_wire_bytes
                      if runner.build_strategy.reduce_strategy == _RS.Reduce
                      else _gc.spmd_allreduce_wire_bytes)
        analytic = (_gc.analytic_wire_bytes(rewritten, dp)
                    or spmd_model(prog, dp))
        comm_fields = {
            "reduce_mode": (plan_fields["plan_point"] if args.auto
                            else args.reduce_mode),
            "total_devices": runner.device_count,
            "grad_bytes_on_wire": analytic["grad_wire_bytes"],
            "param_allgather_bytes_on_wire":
                analytic["param_allgather_wire_bytes"],
            "wire_bytes_per_step": analytic["wire_bytes"],
        }
        if args.tp > 1:
            # tp rows, same discipline as grad_bytes_on_wire: the
            # analytic per-device tp-collective bytes from the rewritten
            # program's spliced tp_* ops (framework/sharding.py ring
            # accounting, shared probe_common.collective_wire_bytes
            # model); None when the SPMD partitioner owns the tp
            # collectives (reduce_mode=allreduce)
            from paddle_tpu.framework.sharding import tp_analytic_wire_bytes
            tpw = tp_analytic_wire_bytes(rewritten, args.tp,
                                         nominal_batch=args.batch_size)
            comm_fields.update({
                "tp": args.tp,
                "tp_allreduce_bytes_on_wire":
                    tpw["tp_allreduce_wire_bytes"] if tpw else None,
                "tp_allgather_bytes_on_wire":
                    tpw["tp_allgather_wire_bytes"] if tpw else None,
                "tp_wire_bytes_per_step":
                    tpw["tp_wire_bytes"] if tpw else None,
                "tp_collective_counts":
                    tpw["tp_op_counts"] if tpw else None,
            })
        if args.pipeline_stages > 1:
            # same discipline as grad_bytes_on_wire: the analytic
            # boundary-transfer model (probe_common ring accounting /
            # collective-permute: one act + one grad buffer per tick),
            # and the exact schedule-table bubble fraction
            from paddle_tpu.parallel.pipeline import (
                pp_boundary_wire_bytes, schedule_census)
            sched_census = schedule_census(args.pipeline_schedule,
                                           args.num_microbatches,
                                           args.pipeline_stages)
            mb_rows = args.batch_size // max(
                1, dp * args.num_microbatches)
            wire = pp_boundary_wire_bytes(rewritten, mb_rows)
            comm_fields.update({
                "pipeline_stages": args.pipeline_stages,
                "num_microbatches": args.num_microbatches,
                "pipeline_schedule": args.pipeline_schedule,
                "bubble_fraction": sched_census["bubble_fraction"],
                "peak_stash_microbatches": sched_census["peak_stash"],
                "pp_boundary_bytes":
                    wire["pp_boundary_bytes"] if wire else None,
            })
        if not args.no_census:
            from probe_common import census_wire_bytes, collective_census
            cs = list(runner._cache.values())[-1]
            # one memoized AOT compile serves the wire census AND the
            # memory census below (Executor._aot_compiled)
            hlo = runner._aot_compiled(cs, feed, scope).as_text()
            census = collective_census(hlo)
            comm_fields["wire_bytes_census"] = int(census_wire_bytes(
                census, dp, min_bytes=8))
            comm_fields["census_collectives"] = {
                k: len(v) for k, v in census.items()}

    # memory + utilization columns (r17): the blocked-measured MFU (the
    # timed window above block_until_ready's, so dt is true step time)
    # and — unless --no_census — the measured memory census of the
    # executable the loop actually ran, next to the static prediction
    from paddle_tpu.framework import costs as _costs
    flops = _costs.program_flops_bytes(
        pt.default_main_program(), nominal_batch=args.batch_size)["flops"]
    ndev = max(1, int(getattr(runner, "device_count", 1)))
    mem_fields = {
        "model_flops_per_step": round(flops),
        "mfu": round(_costs.mfu(flops / ndev, dt / args.iters), 8),
    }
    if not args.no_census:
        census = runner.memory_census(feed=feed)
        pred_mem = _costs.predict(
            runner._prepare_program(pt.default_main_program(),
                                    pt.global_scope())
            if args.update_method == "collective"
            else pt.default_main_program(),
            dp=getattr(runner, "_dp", 1),
            nominal_batch=args.batch_size)["memory"]
        mem_fields.update({
            "mem_state_bytes": round(
                census["state"]["categories"]["state_total"]),
            "mem_temp_bytes": census["xla"]["temp_bytes"],
            "mem_temp_source": census["xla"]["temp_source"],
            "mem_peak_bytes": round(census["peak_bytes"]),
            "mem_predicted_peak_total_bytes":
                pred_mem["peak_total_bytes"],
            "mem_planned_peak_bytes": None,
            "mem_plan_reduction": None,
        })
    if args.memory_plan and not args.no_census:
        # the r18 planned twin: one extra compile of the memory-planned
        # program, censused with the same formula — the MEASURED
        # columns, not the prediction. The measured-step budget is
        # recorded on the plan (it gates candidates only under the
        # mandated-recompute mode; the default CSE-able plan is
        # time-safe by construction)
        from paddle_tpu.framework.passes import get_pass
        budget_s = 0.02 * dt / args.iters
        if args.update_method == "collective":
            import dataclasses
            bst2 = dataclasses.replace(
                runner.build_strategy, memory_plan=True,
                memory_plan_time_budget_s=budget_s)
            from paddle_tpu.parallel import ParallelExecutor
            twin = ParallelExecutor(loss_name=loss.name,
                                    build_strategy=bst2,
                                    mesh=runner.mesh)
            jax.block_until_ready(twin.run(feed=feed, fetch_list=[loss],
                                           return_numpy=False))
            census2 = twin.memory_census(feed=feed)
            planned_peak = census2["peak_bytes"]
        else:
            planned_prog = get_pass(
                "memory_plan_pass", nominal_batch=args.batch_size,
                time_budget_s=budget_s)(pt.default_main_program())
            twin = pt.Executor()
            jax.block_until_ready(twin.run(
                program=planned_prog, feed=feed, fetch_list=[loss],
                return_numpy=False))
            census2 = twin.memory_census(feed=feed,
                                         program=planned_prog)
            planned_peak = census2["peak_bytes"]
        mem_fields.update({
            "mem_planned_peak_bytes": round(planned_peak),
            "mem_plan_reduction": round(
                1.0 - planned_peak / max(census["peak_bytes"], 1.0), 4),
        })

    unit = ("tokens/sec" if args.model in
            ("transformer", "machine_translation") else "examples/sec")
    print(json.dumps({
        "model": args.model,
        "update_method": args.update_method,
        "rank": denv.trainer_id,
        "nproc": denv.num_trainers,
        "batch_size": args.batch_size,
        "iters": args.iters,
        "latency_ms": round(dt / args.iters * 1000, 3),
        "span_ms": span_ms,
        "throughput": round(units_per_step * args.iters / dt, 2),
        "unit": unit,
        "device": jax.devices()[0].platform,
        **mem_fields,
        **comm_fields,
        **plan_fields,
    }))


if __name__ == "__main__":
    main()
