"""Probe: prefetcher link utilization, stream scaling, and drain ceilings.

ONE flag-driven probe (the r12 numbered-copy consolidation pattern;
probe_prefetch2.py folded in here). `--exp` selects the methodology,
names preserving the lineage:

  utilization   (the original probe_prefetch, r4): raw uint8 h2d staging
                bandwidth measured BEFORE AND AFTER the DevicePrefetcher-
                fed ResNet bs128 train loop, so the fed rate is judged
                against the link speed of the SAME session (VERDICT
                r3 weak #1 was exactly a fed number divided by another
                window's link measure).
  streams       (probe_prefetch2 part 1, r4 follow-up): raw uint8 link
                at 1/2/3 concurrent put streams + the float->uint8
                conversion cost on the staging thread.
  drain         (probe_prefetch2 part 2): drain-only DevicePrefetcher
                rates (no training step) at several (stage_threads,
                capacity) settings — the pipeline's own ceiling.

    python tools/probe_prefetch.py --exp utilization
"""
import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def link_mbps(batch=128, reps=3):
    import jax

    x = (np.random.RandomState(0).rand(batch, 224, 224, 3) * 255
         ).astype("uint8")
    d = jax.device_put(x)
    _ = np.asarray(d[0, 0, 0, 0])
    best = None
    for _ in range(reps):
        t0 = time.time()
        d = jax.device_put(x)
        _ = np.asarray(d[0, 0, 0, 0])
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    return x.nbytes / best / 1e6


def exp_utilization(batch=128, iters=16):
    """Fed-rate vs same-session link: the original probe_prefetch."""
    import jax.numpy as jnp

    sys.path.insert(0, "/root/repo")
    import bench

    link_before = link_mbps(batch)

    exe, loss = bench._build_resnet_train(batch)
    # warm the compiled step with a staged batch
    rng = np.random.RandomState(0)
    feed0 = {
        "img": jnp.asarray((rng.rand(batch, 224, 224, 3) * 255)
                           .astype("uint8")),
        "label": jnp.asarray(rng.randint(0, 1000, (batch, 1))
                             .astype("int64")),
    }
    out = exe.run(feed=feed0, fetch_list=[loss], return_numpy=False)
    float(out[0])

    from paddle_tpu.data.feeder import staging_specs
    from paddle_tpu.data.prefetch import DevicePrefetcher

    host_batches = [
        {"img": rng.rand(batch, 224, 224, 3).astype("float32"),
         "label": rng.randint(0, 1000, (batch, 1)).astype("int64")}
        for _ in range(4)
    ]
    specs = staging_specs()

    results = {}
    for cap in (2, 4):
        def feed_iter():
            for i in range(iters + 2):
                yield host_batches[i % len(host_batches)]

        pf = iter(DevicePrefetcher(feed_iter, capacity=cap, staging=specs))
        for _ in range(2):
            out = exe.run(feed=next(pf), fetch_list=[loss],
                          return_numpy=False)
        float(out[0])
        fetched = []
        t0 = time.time()
        for feed in pf:
            out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
            fetched.append(out[0])
        float(fetched[-1])
        dt = time.time() - t0
        rate = batch * len(fetched) / dt
        results[f"cap{cap}_imgs_s"] = round(rate, 2)
        results[f"cap{cap}_wire_MBps"] = round(
            rate * 224 * 224 * 3 / 1e6, 2)

    link_after = link_mbps(batch)
    results["link_before_MBps"] = round(link_before, 1)
    results["link_after_MBps"] = round(link_after, 1)
    link = max(link_before, link_after)
    results["utilization_cap2"] = round(
        results["cap2_wire_MBps"] / link, 3)
    results["utilization_cap4"] = round(
        results["cap4_wire_MBps"] / link, 3)
    return results


def exp_streams(batch=128):
    """Concurrent-stream link scaling + staging conversion cost (the
    first half of the former probe_prefetch2)."""
    import jax

    img_u8 = (np.random.RandomState(0).rand(batch, 224, 224, 3) * 255
              ).astype("uint8")
    nbytes = img_u8.nbytes

    d = jax.device_put(img_u8)
    _ = np.asarray(d[0, 0, 0, 0])

    out = {}

    def put_one(x):
        h = jax.device_put(x)
        _ = np.asarray(h[0, 0, 0, 0])
        return h

    for streams in (1, 2, 3):
        pool = ThreadPoolExecutor(max_workers=streams)
        reps = 6
        best = None
        for _ in range(2):
            t0 = time.time()
            futs = [pool.submit(put_one, img_u8) for _ in range(reps)]
            for f in futs:
                f.result()
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
        out[f"link_MBps_{streams}stream"] = round(
            nbytes * reps / best / 1e6, 2)
        pool.shutdown()

    # conversion cost on the staging thread (fp32 batch -> uint8 wire)
    img_f32 = np.random.RandomState(1).rand(batch, 224, 224, 3).astype(
        "float32")
    t0 = time.time()
    for _ in range(5):
        w = (img_f32 * 255.0).astype("uint8")  # noqa: F841
    out["convert_ms_per_batch"] = round((time.time() - t0) / 5 * 1e3, 1)
    return out


def exp_drain(batch=128):
    """Drain-only prefetcher ceilings (the second half of the former
    probe_prefetch2): no training step, just the pipeline."""
    import paddle_tpu as pt  # noqa: F401  (registers staging helpers)
    from paddle_tpu.data.prefetch import DevicePrefetcher

    out = {}
    host_batches = [
        {"img": np.random.RandomState(i).rand(batch, 224, 224, 3)
         .astype("float32"),
         "label": np.random.RandomState(i).randint(0, 1000, (batch, 1))
         .astype("int64")}
        for i in range(4)
    ]
    specs = {"img": ("uint8", 1.0 / 255.0)}

    def feed_iter():
        for i in range(12):
            yield host_batches[i % 4]

    for threads, cap in ((1, 4), (2, 4), (3, 6), (4, 8)):
        best = None
        for _ in range(2):
            pf = iter(DevicePrefetcher(feed_iter, capacity=cap,
                                       staging=specs,
                                       stage_threads=threads))
            first = next(pf)  # warm
            _ = np.asarray(first["img"][0, 0, 0, 0])
            t0 = time.time()
            n = 0
            last = None
            for b in pf:
                last = b
                n += 1
            _ = np.asarray(last["img"][0, 0, 0, 0])
            dt = time.time() - t0
            rate = n * batch / dt
            best = rate if best is None else max(best, rate)
        out[f"drain_imgs_per_s_t{threads}_c{cap}"] = round(best, 2)
        out[f"drain_wire_MBps_t{threads}_c{cap}"] = round(
            best * 224 * 224 * 3 / 1e6, 2)
    return out


EXPERIMENTS = {"utilization": exp_utilization, "streams": exp_streams,
               "drain": exp_drain}


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--exp", choices=sorted(EXPERIMENTS),
                   default="utilization")
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--iters", type=int, default=16,
                   help="utilization: fed train steps per capacity")
    args = p.parse_args()
    if args.exp == "utilization":
        results = exp_utilization(args.batch, args.iters)
    else:
        results = EXPERIMENTS[args.exp](args.batch)
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
