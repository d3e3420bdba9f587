"""Benchmark: ResNet-50 synthetic-ImageNet training throughput on one chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "evidence"}.

vs_baseline compares against the reference's best published in-repo ResNet-50
training number (84.08 images/sec, 2-socket Xeon 6148 MKL-DNN bs=256 —
reference benchmark/IntelOptimizedPaddle.md:39-45; the reference publishes no
Fluid-GPU tables, see BASELINE.md).

The evidence block makes the headline auditable (≙ the hardware context the
reference publishes next to its tables, reference benchmark/README.md:33-39):
  - flops_per_step from XLA's own cost model (Executor.cost_analysis), so
    implied TFLOP/s and MFU vs the chip's bf16 peak can be checked;
  - loss_first/loss_last over the timed window with a convergent lr, so the
    timed steps are demonstrably real training (fwd+bwd+update), not a
    degenerate or dead-code-eliminated loop;
  - a DevicePrefetcher-fed variant over distinct host batches, so the input
    pipeline (host->device staging) is measured, not bypassed;
  - blocked per-step latency alongside pipelined throughput: async
    pipelining through the functional state chain is what a real input loop
    achieves, the blocked step adds the dispatch and fetch round trip;
  - a Pallas flash-attention vs XLA-composite micro-bench (fwd+bwd), the
    number that justifies the hand-written kernel (SURVEY §7 stage 4).
"""

from __future__ import annotations

import json
import time

import numpy as np

BASELINE_IMGS_PER_SEC = 84.08
# reference's best published ResNet-50 INFERENCE number (bs16, same table)
INFER_BASELINE_IMGS_PER_SEC = 217.69

def _chip_specs(device):
    """(bf16 peak TFLOP/s, HBM GB/s) of the chip, from the repo's one
    device_kind table. A kind the table lacks is an error: a utilization
    against a guessed peak is not a measurement."""
    from paddle_tpu.framework.costs import device_peaks

    peaks = device_peaks(device.device_kind)
    if peaks is None:
        raise RuntimeError(
            f"no peaks recorded for device_kind {device.device_kind!r} "
            f"(paddle_tpu/framework/costs.py DEVICE_PEAKS); add the chip's "
            f"published peaks there before benchmarking on it")
    return peaks["peak_flops"] / 1e12, peaks["hbm_bps"] / 1e9


def _build_resnet_train(batch: int, depth: int = 50):
    import paddle_tpu as pt
    from paddle_tpu import models

    pt.reset_default_programs()
    pt.reset_global_scope()
    # img declares uint8 staging: fp32 feeding (synthetic variant) compiles
    # with no cast; the prefetcher variant feeds uint8 so only 1/4 of the
    # fp32 bytes cross the host->device link, with the dequant compiled
    # into the step (layers.data staging_dtype, tests/test_staging.py)
    img = pt.layers.data(name="img", shape=[224, 224, 3],
                         staging_dtype="uint8")
    loss, acc, _ = models.resnet.resnet_imagenet(
        img=img, depth=depth, is_test=False, data_format="NHWC",
        use_bf16=True)
    # lr must be convergent at this batch size: the timed window doubles as
    # the work-verification window (loss must decrease during it).
    opt = pt.optimizer.MomentumOptimizer(learning_rate=3e-3, momentum=0.9)
    opt.minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return exe, loss


N_DISTINCT_BATCHES = 8


def _staged_batches(batch: int, n: int = N_DISTINCT_BATCHES, seed: int = 0):
    """n DISTINCT pre-staged device batches with labels that are a real
    function of the images (mean-brightness bucket over 1000 classes), so
    every timed step does full fwd+bwd on data it has not necessarily seen
    and the task is learnable — the same audit property
    tools/bench_breadth.py carries (VERDICT r4 #4: the flagship number must
    not train on one staged batch)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        label = rng.randint(0, 1000, (batch, 1)).astype("int64")
        # class id encoded as a global brightness offset (0.3 dynamic range
        # vs noise-mean sigma ~0.001): strong enough signal that the
        # 60-step timed window demonstrably learns across ALL 8 batches
        img = (rng.rand(batch, 224, 224, 3) * 0.7
               + (label / 1000.0)[:, :, None, None] * 0.3).astype("float32")
        out.append({"img": jnp.asarray(img), "label": jnp.asarray(label)})
    return out


def _resnet_throughput(batch: int, iters: int):
    """Pipelined steady-state throughput over 8 distinct pre-staged
    batches; returns (imgs/sec, blocked_step_ms, losses, flops_per_step,
    bytes_accessed, (exe, loss)).

    Sync discipline: the barrier is host-value realization (float(...) of
    a fetched loss). The loss of step k depends on step k-1's updated
    parameters, so realizing the final loss bounds all timed steps.
    """
    exe, loss = _build_resnet_train(batch)
    feeds = _staged_batches(batch)

    out = exe.run(feed=feeds[0], fetch_list=[loss], return_numpy=False)
    float(out[0])  # compile + drain: queue is empty past this point

    # blocked latency: one fully-synchronized step (dispatch + execute + fetch
    # round-trip)
    t0 = time.time()
    out = exe.run(feed=feeds[0], fetch_list=[loss], return_numpy=False)
    float(out[0])
    blocked_ms = (time.time() - t0) * 1e3

    # best of 3 windows. Losses are tracked across ALL windows (training
    # continues through every one), so the work-verification property is
    # unchanged.
    losses, dt = [], None
    for _ in range(3):
        fetched = []
        t0 = time.time()
        for i in range(iters):
            out = exe.run(feed=feeds[i % len(feeds)], fetch_list=[loss],
                          return_numpy=False)
            fetched.append(out[0])
        float(fetched[-1])  # realization barrier
        w = time.time() - t0
        dt = w if dt is None else min(dt, w)
        losses.extend(float(x) for x in fetched)

    ca = exe.cost_analysis(feed=feeds[0], fetch_list=[loss])
    flops = float(ca.get("flops", 0.0)) if ca else 0.0
    bytes_accessed = float(ca.get("bytes accessed", 0.0)) if ca else 0.0
    return (batch * iters / dt, blocked_ms, losses, flops, bytes_accessed,
            (exe, loss))


def _best_of(n_windows: int, window_fn):
    """max of n timing windows."""
    best = None
    for _ in range(n_windows):
        rate = window_fn()
        best = rate if best is None else max(best, rate)
    return best


def interleaved_best(runners: dict, rounds: int = 3) -> dict:
    """{name: run_fn} -> {name: min seconds} over alternating rounds, so
    neither side of an A/B owns the warmer half of the run (shared by the
    flash micro and tools/bench_longctx.py)."""
    best = {k: None for k in runners}
    for _ in range(rounds):
        for name, run in runners.items():
            dt = run()
            best[name] = dt if best[name] is None else min(best[name], dt)
    return best


def _link_reconciliation(link_samples, rate_per_sec,
                         wire_bytes_per_unit=224 * 224 * 3):
    """Shared link-utilization discipline (prefetcher + serving): capacity
    estimate = the FASTEST same-run link sample (the burst probe is a LOWER
    bound on capacity, so utilization can exceed 1.0 — meaning the
    sustained pipeline itself is the best link measurement available)."""
    link = float(np.max(link_samples))
    wire_mbps = rate_per_sec * wire_bytes_per_unit / 1e6
    return link, (wire_mbps / link if link else 0.0)


def _resnet_infer_throughput(batch: int = 16, iters: int = 30):
    """Inference img/s (is_test graph, batch-stat-free BN): the reference
    publishes ResNet-50 INFER bs16 = 217.69 img/s as its best in-repo
    number (reference benchmark/IntelOptimizedPaddle.md:81-87).

    Sync discipline: inference steps have no parameter-update chain, so a
    data dependency is created explicitly — step k's input derives from
    step k-1's output — making the final realization bound every timed
    step (same reasoning as the train bench)."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import models

    pt.reset_default_programs()
    pt.reset_global_scope()
    img = pt.layers.data(name="img", shape=[224, 224, 3],
                         staging_dtype="uint8")
    loss, acc, logits = models.resnet.resnet_imagenet(
        img=img, depth=50, is_test=True, data_format="NHWC", use_bf16=True)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(3)
    img0 = jnp.asarray(rng.rand(batch, 224, 224, 3).astype("float32"))
    label = jnp.asarray(rng.randint(0, 1000, (batch, 1)).astype("int64"))
    out = exe.run(feed={"img": img0, "label": label}, fetch_list=[logits],
                  return_numpy=False)
    float(out[0][0, 0])

    def window():
        cur = img0
        t0 = time.time()
        out = None
        for _ in range(iters):
            out = exe.run(feed={"img": cur, "label": label},
                          fetch_list=[logits], return_numpy=False)
            # negligible (1e-30-scaled) but real dependency on the output
            cur = img0 + out[0][0, 0].astype(jnp.float32) * 1e-30
        float(out[0][0, 0])
        return batch * iters / (time.time() - t0)

    return _best_of(3, window)


def _resnet_served_throughput(batch: int = 16, n_requests: int = 32,
                              inflight: int = 8):
    """Server-mode inference throughput: a PredictorServer fields PIPELINED
    requests (≙ reference api_impl.cc:126 long-lived predictor; the
    conservative number below chains each request on the previous
    response, paying the full per-request round trip every time). With
    `inflight` requests outstanding on one connection, client IO, host->
    device staging (uint8 wire) and TPU compute overlap — the serving
    stack's real capacity."""
    import paddle_tpu as pt
    from paddle_tpu import models
    from paddle_tpu.serving import PredictorClient, PredictorServer

    pt.reset_default_programs()
    pt.reset_global_scope()
    img = pt.layers.data(name="img", shape=[224, 224, 3],
                         staging_dtype="uint8")
    loss, acc, logits = models.resnet.resnet_imagenet(
        img=img, depth=50, is_test=True, data_format="NHWC", use_bf16=True)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    program = pt.default_main_program()
    scope = pt.global_scope()

    class _Served:
        fetch_names = [logits.name]

        def run(self, feed, fetch_names=None, return_numpy=True):
            feed = dict(feed)
            feed.setdefault("label", np.zeros((batch, 1), "int64"))
            return exe.run(program=program, feed=feed,
                           fetch_list=list(fetch_names or self.fetch_names),
                           scope=scope, return_numpy=return_numpy)

    rng = np.random.RandomState(5)
    reqs = [(rng.rand(batch, 224, 224, 3) * 255).astype("uint8")
            for _ in range(4)]
    # same-run link sample (same uint8 wire format, prefetcher-style
    # concurrency) bracketing the serving windows: the serving number's
    # reconciliation metric (VERDICT r4 #8) — without it, 22 img/s next to
    # 658 direct reads as a 30x serving penalty when it is transport-bound
    link_samples = [_uint8_link_mbps(batch)]
    rates = []
    with PredictorServer(_Served()) as srv:
        host, port = srv.address
        with PredictorClient(host, port) as c:
            c.infer({"img": reqs[0]})  # compile + warm
            for _ in range(3):
                t0 = time.time()
                sent = recvd = 0
                while recvd < n_requests:
                    while sent < n_requests and sent - recvd < inflight:
                        c.send({"img": reqs[sent % len(reqs)]})
                        sent += 1
                    c.recv()
                    recvd += 1
                rates.append(batch * n_requests / (time.time() - t0))
    link_samples.append(_uint8_link_mbps(batch))
    best = max(rates)
    link, util = _link_reconciliation(link_samples, best)
    # per-window utilizations against the same link estimate: the
    # serving number's error bar (VERDICT r5 #4 — a 0.54-0.71 spread was
    # committed as a single point)
    utils = [_link_reconciliation(link_samples, r)[1] for r in rates]
    return best, link, util, utils


def _h2d_bandwidth_mbps(batch: int) -> float:
    """Host->device staging bandwidth for one image batch (the prefetcher
    variant is bounded by this; on a TPU host it is PCIe/DMA)."""
    import jax

    x = np.random.rand(batch, 224, 224, 3).astype("float32")
    d = jax.device_put(x)
    float(d[0, 0, 0, 0])
    t0 = time.time()
    for _ in range(2):
        d = jax.device_put(x)
        float(d[0, 0, 0, 0])
    dt = (time.time() - t0) / 2
    return x.nbytes / dt / 1e6


def _uint8_link_mbps(batch: int, streams: int = 4, reps: int = 12) -> float:
    """Raw h2d bandwidth for the PREFETCHER'S OWN wire format (a uint8
    image batch) at the SAME transfer concurrency the prefetcher uses.

    A single-stream denominator would understate what concurrent transfers
    achieve on a link with per-transfer latency and let utilization exceed
    1; matching the pipeline's concurrency makes the ratio honest."""
    import jax
    from concurrent.futures import ThreadPoolExecutor

    x = (np.random.RandomState(9).rand(batch, 224, 224, 3) * 255
         ).astype("uint8")
    d = jax.device_put(x)
    _ = np.asarray(d[0, 0, 0, 0])

    def put_one():
        h = jax.device_put(x)
        _ = np.asarray(h[0, 0, 0, 0])

    best = None
    with ThreadPoolExecutor(max_workers=streams) as pool:
        for _ in range(2):
            t0 = time.time()
            futs = [pool.submit(put_one) for _ in range(reps)]
            for f in futs:
                f.result()
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
    return x.nbytes * reps / best / 1e6


def _resnet_prefetcher_throughput(batch: int, iters: int, exe, loss):
    """Throughput with the real input pipeline: distinct host batches
    converted to uint8 on DevicePrefetcher's staging threads and put to
    device byte-lean (1/4 of the fp32 footprint), with the dequant compiled
    into the step. The uint8 feed signature compiles one new executable for
    the same (exe, loss) program; the warmup loop absorbs it.

    Returns (imgs_per_sec, link_MBps, utilization): the link is measured
    IMMEDIATELY before and after the fed windows with the same wire format
    and the same 4-stream concurrency, and utilization = fed wire rate /
    BEST link sample (see the capacity-estimate comment below): a fed rate
    is only comparable with a link measured in the same run."""
    from paddle_tpu.data.feeder import staging_specs
    from paddle_tpu.data.prefetch import DevicePrefetcher

    rng = np.random.RandomState(1)
    host_batches = [
        {"img": rng.rand(batch, 224, 224, 3).astype("float32"),
         "label": rng.randint(0, 1000, (batch, 1)).astype("int64")}
        for _ in range(4)
    ]
    specs = staging_specs()  # img -> uint8 on the staging threads

    def feed_iter():
        for i in range(iters + 2):
            yield host_batches[i % len(host_batches)]

    link_samples = [_uint8_link_mbps(batch)]
    best = None
    for window in range(2):  # best of 2 (each pass restages every batch)
        pf = iter(DevicePrefetcher(feed_iter, capacity=8, staging=specs,
                                   stage_threads=4))
        for _ in range(2):  # warmup (compile happens on the very first)
            out = exe.run(feed=next(pf), fetch_list=[loss],
                          return_numpy=False)
        float(out[0])

        fetched = []
        t0 = time.time()
        for feed in pf:
            out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
            fetched.append(out[0])
        float(fetched[-1])
        rate = batch * len(fetched) / (time.time() - t0)
        best = rate if best is None else max(best, rate)
        link_samples.append(_uint8_link_mbps(batch))
    link, util = _link_reconciliation(link_samples, best)
    return best, link, util


def _flash_attention_speedup(seq_len: int = 8192, heads: int = 8,
                             head_dim: int = 128, batch: int = 1):
    """Pallas flash attention vs the XLA composite, fwd+bwd wall clock.

    T=8192 is where the O(T) kernel earns its keep on a v5e: the composite's
    [T, T] score materialization pushes HBM to the limit (it OOMs outright at
    T=16384 where the flash kernel still runs)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(2)
    shape = (batch, heads, seq_len, head_dim)
    q = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype=jnp.bfloat16)
    scale = 1.0 / np.sqrt(head_dim)

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, scale=scale, causal=True)
                       .astype(jnp.float32))

    def loss_ref(q, k, v):
        return jnp.sum(pk._attention_reference(q, k, v, scale, causal=True)
                       .astype(jnp.float32))

    def make(fn):
        g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
        out = g(q, k, v)
        float(out[0][0, 0, 0, 0])  # compile + drain (realization barrier)

        def run():
            t0 = time.time()
            for _ in range(5):
                out = g(q, k, v)
            float(out[0][0, 0, 0, 0])  # device queue FIFO: bounds all 5
            return (time.time() - t0) / 5
        return run

    # a kernel that fails to compile or a composite that runs out of memory
    # raises: the run must not exit 0 with a dead kernel in its evidence
    run_flash = make(loss_flash)
    run_ref = make(loss_ref)
    # interleaved rounds + per-side best: neither side owns the warmer
    # half of the measurement
    t_flash = t_ref = None
    for _ in range(3):
        tf, tr = run_flash(), run_ref()
        t_flash = tf if t_flash is None else min(t_flash, tf)
        t_ref = tr if t_ref is None else min(t_ref, tr)
    # emit the raw per-side times: a bare ratio is unauditable
    return {"speedup": round(t_ref / t_flash, 3),
            "flash_ms": round(t_flash * 1e3, 2),
            "composite_ms": round(t_ref * 1e3, 2)}


def _dp_comm_wire_evidence(dp: int = 8) -> dict:
    """Per-device gradient bytes-on-wire per step for the current default
    main program (the last-built ResNet train step) under the three
    reduce modes — ring accounting, parallel/grad_comm.py's model."""
    import paddle_tpu as pt
    from paddle_tpu.parallel.collective import compressed_size_ratio
    from paddle_tpu.parallel.grad_comm import spmd_allreduce_wire_bytes

    ar = spmd_allreduce_wire_bytes(pt.default_main_program(), dp)
    g = ar["grad_wire_bytes"]
    return {
        "allreduce": g,
        "reduce_scatter": g // 2,           # the AG half becomes params
        "quantized_int8_block256": int(g // 2
                                       * compressed_size_ratio("int8", 256)),
    }


def main():
    import jax

    dev = jax.devices()[0]
    platform = dev.platform
    if platform == "cpu":
        raise SystemExit(
            "bench.py measures a chip and JAX found only the CPU: a CPU "
            "timing is not a result. Run it through the chip tool.")
    peak_tflops, hbm_gbps = _chip_specs(dev)

    main_bs, alt_bs, iters = 256, 128, 20

    imgs_s, blocked_ms, losses, flops, bytes_acc, _ = _resnet_throughput(
        main_bs, iters)
    alt_imgs_s, _, _, _, _, (alt_exe, alt_loss) = _resnet_throughput(
        alt_bs, iters)
    pf_imgs_s, pf_link_mbps, pf_util = _resnet_prefetcher_throughput(
        alt_bs, iters, alt_exe, alt_loss)
    infer_bs16 = _resnet_infer_throughput(16, 30)
    (served_bs16, served_link_mbps, served_util,
     served_utils) = _resnet_served_throughput(16, 32, 8)
    h2d_mbps = _h2d_bandwidth_mbps(alt_bs)
    flash_speedup = _flash_attention_speedup()

    loss_first, loss_last = losses[0], losses[-1]
    if not loss_last < loss_first:  # not assert: must survive python -O
        raise RuntimeError(
            f"loss did not decrease over the timed window "
            f"({loss_first:.3f} -> {loss_last:.3f}); benchmark invalid")

    implied_tflops = flops * imgs_s / main_bs / 1e12 if flops else None
    # step-time breakdown vs the chip rooflines (round-3 attribution,
    # VERDICT r2 #1): ideal_hbm_ms is XLA's own bytes-accessed estimate at
    # the chip's HBM bandwidth; roofline_fraction ~1.0 means the step IS
    # the memory roofline — on a v5e (197 TFLOP/s : 819 GB/s = 240
    # flops/byte) ResNet-50's arithmetic intensity (~75 flops/byte) makes
    # the HBM roofline, not the MXU, the binding limit. Per-call dispatch
    # measured separately at ~3 ms (scan-fused in-graph loop differs from
    # the host loop by that much; tools/profile_resnet.py).
    step_ms = main_bs / imgs_s * 1e3
    breakdown = None
    if flops and peak_tflops:
        breakdown = {
            "measured_step_ms": round(step_ms, 1),
            "ideal_mxu_ms": round(flops / (peak_tflops * 1e12) * 1e3, 1),
        }
        if bytes_acc and hbm_gbps:
            ideal_hbm = bytes_acc / (hbm_gbps * 1e9) * 1e3
            breakdown["bytes_accessed_xla"] = bytes_acc
            breakdown["ideal_hbm_ms"] = round(ideal_hbm, 1)
            breakdown["hbm_roofline_fraction"] = round(ideal_hbm / step_ms,
                                                       3)
    evidence = {
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "flops_per_step_xla": flops,
        "implied_tflops": round(implied_tflops, 2) if implied_tflops else None,
        "peak_bf16_tflops": peak_tflops,
        "mfu": (round(implied_tflops / peak_tflops, 4)
                if implied_tflops and peak_tflops else None),
        "loss_first": round(loss_first, 4),
        "loss_last": round(loss_last, 4),
        "n_distinct_batches": N_DISTINCT_BATCHES,
        "blocked_step_ms": round(blocked_ms, 1),
        "step_time_breakdown": breakdown,
        f"images_per_sec_bs{alt_bs}": round(alt_imgs_s, 2),
        f"prefetcher_fed_images_per_sec_bs{alt_bs}": round(pf_imgs_s, 2),
        # link measured in the SAME run with the same uint8 wire format and
        # the same 4-stream concurrency (before + after the fed windows,
        # best sample): the utilization is the framework-controlled
        # number. Values >1.0 mean the sustained pipeline beat the burst
        # probe — the probe is a lower bound on capacity
        "prefetcher_same_run_link_MBps": round(pf_link_mbps, 2),
        "prefetcher_link_utilization": round(pf_util, 3),
        "staged_wire_bytes_per_image": 224 * 224 * 3,
        "fp32_wire_bytes_per_image": 224 * 224 * 3 * 4,
        "infer_images_per_sec_bs16": round(infer_bs16, 2),
        # server-mode (PredictorServer, 8 pipelined requests in flight on
        # one connection): what the serving stack sustains when requests
        # overlap, vs the conservative chained-RTT number above
        "infer_images_per_sec_served_pipelined_bs16": round(served_bs16, 2),
        # serving reconciliation: fraction of the same-run h2d link the
        # served wire rate consumes (>0.7 = the server is transport-bound,
        # not compute- or framework-bound)
        "served_same_run_link_MBps": round(served_link_mbps, 2),
        "served_link_utilization": round(served_util, 3),
        # per-window utilizations + half-spread error bar
        "served_link_utilization_runs": [round(u, 3) for u in served_utils],
        "served_link_utilization_error_bar": round(
            (max(served_utils) - min(served_utils)) / 2, 3),
        "infer_vs_reference_best": round(
            infer_bs16 / INFER_BASELINE_IMGS_PER_SEC, 3),
        "infer_reference_best_images_per_sec":
            INFER_BASELINE_IMGS_PER_SEC,
        "h2d_staging_MBps": round(h2d_mbps, 1),
        "flash_attention_fwd_bwd_speedup_vs_xla_T8192": flash_speedup,
        # data-parallel scale-out wire cost of THIS flagship step (ISSUE
        # r8): analytic ring model over the program's trainable params.
        # ResNet's batch_norm keeps it on the SPMD allreduce path (the
        # explicit pipeline rejects batch-global ops), so reduce_scatter/
        # quantized rows are the analytic what-if for this param set; the
        # measured A/B lives in BENCH_DP_r08.json on the BN-free configs.
        "dp8_grad_wire_bytes_per_step": _dp_comm_wire_evidence(),
    }
    print(json.dumps({
        "metric": f"resnet50_train_images_per_sec_bs{main_bs}_{platform}",
        "value": round(imgs_s, 2),
        "unit": "images/sec",
        "vs_baseline": round(imgs_s / BASELINE_IMGS_PER_SEC, 3),
        "evidence": evidence,
    }))


if __name__ == "__main__":
    main()
