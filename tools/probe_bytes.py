"""Quick probe: compile the flagship train step on the TPU and report XLA
cost-analysis bytes-accessed/flops + a short timed window.

Usage: python tools/probe_bytes.py
"""
import json
import sys
import time

import numpy as np


def main(batch=256, iters=10):
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
    out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    float(out[0])
    ca = exe.cost_analysis(feed=feed, fetch_list=[loss])
    flops = float(ca.get("flops", 0.0)) if ca else 0.0
    bytes_acc = float(ca.get("bytes accessed", 0.0)) if ca else 0.0

    best = None
    losses = []
    for _ in range(3):
        fetched = []
        t0 = time.time()
        for _ in range(iters):
            out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
            fetched.append(out[0])
        float(fetched[-1])
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
        losses.extend(float(x) for x in fetched)
    step_ms = best / iters * 1e3
    imgs_s = batch / (best / iters)
    print(json.dumps({
        "bytes_accessed_xla": bytes_acc,
        "bytes_GB": round(bytes_acc / 1e9, 2),
        "flops_per_step": flops,
        "step_ms": round(step_ms, 1),
        "images_per_sec": round(imgs_s, 1),
        "implied_tflops": round(flops / (best / iters) / 1e12, 2),
        "mfu_v5e": round(flops / (best / iters) / 197e12, 4),
        "ideal_hbm_ms": round(bytes_acc / 819e9 * 1e3, 1),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
    }))


if __name__ == "__main__":
    main()
