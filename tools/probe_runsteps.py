"""Probe: does the scan-fused run_steps loop beat the host-loop throughput
on the flagship? (It removes the per-step Python round trip and dispatch.)

    python tools/probe_runsteps.py
"""
import json
import sys
import time

import numpy as np


def main(batch=256, k=10, windows=3):
    import jax.numpy as jnp

    sys.path.insert(0, "/root/repo")
    import bench

    exe, loss = bench._build_resnet_train(batch)
    rng = np.random.RandomState(0)
    feed = {
        "img": jnp.asarray(rng.rand(batch, 224, 224, 3).astype("float32")),
        "label": jnp.asarray(
            rng.randint(0, 1000, (batch, 1)).astype("int64")),
    }
    feed_list = [feed] * k

    # host loop reference
    out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    float(out[0])

    def host_window():
        t0 = time.time()
        fetched = []
        for _ in range(k):
            o = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
            fetched.append(o[0])
        float(fetched[-1])
        return (time.time() - t0) / k

    out = exe.run_steps(feed_list, fetch_list=[loss], return_numpy=False)
    float(np.asarray(out[0])[-1])  # compile + drain

    def scan_window():
        t0 = time.time()
        o = exe.run_steps(feed_list, fetch_list=[loss], return_numpy=False)
        float(np.asarray(o[0])[-1])
        return (time.time() - t0) / k

    best = {"host": None, "scan": None}
    for _ in range(windows):
        for name, fn in (("host", host_window), ("scan", scan_window)):
            dt = fn()
            best[name] = dt if best[name] is None else min(best[name], dt)
    print(json.dumps({
        "host_step_ms": round(best["host"] * 1e3, 1),
        "scan_step_ms": round(best["scan"] * 1e3, 1),
        "host_imgs_s": round(batch / best["host"], 1),
        "scan_imgs_s": round(batch / best["scan"], 1),
    }))


if __name__ == "__main__":
    main()
