"""Attribute the DeepFM sparse train step's time on the TPU.

Builds the driver-config-#5 step (bs4096, vocab 1M, 39 fields, is_sparse),
dumps the optimized HLO, and ranks top-level instructions by the conv/fusion
backend_config's own `estimated_cycles`, bucketing by op_name metadata. Also
times the step and prints cost-analysis totals.

    python tools/probe_deepfm.py
"""

from __future__ import annotations

import collections
import json
import re
import time

import numpy as np


def build(b=4096, vocab=1000000, sparse=True, row_pad=None):
    import paddle_tpu as pt
    from paddle_tpu.models import deepfm

    pt.reset_default_programs()
    pt.reset_global_scope()
    rng = np.random.RandomState(0)
    with pt.core.unique_name.guard():
        loss, _ = deepfm.deepfm(num_fields=39, vocab_size=vocab,
                                is_sparse=sparse, row_pad=row_pad)
        opt = pt.optimizer.AdamOptimizer(learning_rate=3e-4)
        opt.minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    import jax.numpy as jnp
    feed = {"feat_ids": jnp.asarray(
                rng.randint(0, vocab, (b, 39)).astype("int64")),
            "feat_vals": jnp.asarray(rng.rand(b, 39).astype("float32")),
            "label": jnp.asarray(
                rng.randint(0, 2, (b, 1)).astype("float32"))}
    return exe, loss, feed, pt.default_main_program(), pt.global_scope()


def analyze(tag, sparse, row_pad=None):
    import jax.numpy as jnp

    exe, loss, feed, prog, scope = build(sparse=sparse, row_pad=row_pad)
    compiled = exe._lookup_or_compile(prog, feed, [loss.name], scope)
    feed_vals = tuple(jnp.asarray(feed[n]) for n in compiled.feed_names)
    ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
    rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
    ex = compiled.fn.lower(feed_vals, ro_vals, rw_vals,
                           np.uint32(0)).compile()
    hlo = ex.as_text()
    with open(f"/tmp/deepfm_{tag}.hlo", "w") as f:
        f.write(hlo)

    rows = []
    for line in hlo.splitlines():
        mcy = re.search(r'"estimated_cycles":"(\d+)"', line)
        if not mcy:
            continue
        cyc = int(mcy.group(1))
        mop = re.match(r"\s+%?([\w.\-]+)\s*=", line)
        mmeta = re.search(r'op_name="([^"]*)"', line)
        rows.append((cyc, mop.group(1) if mop else "?",
                     mmeta.group(1)[:90] if mmeta else ""))
    rows.sort(reverse=True)
    total_cyc = sum(r[0] for r in rows)

    buckets = collections.Counter()
    for cyc, name, meta in rows:
        key = "other"
        for pat in ("sort", "scatter", "gather", "dot", "reduce",
                    "transpose", "convert", "iota", "unique", "while",
                    "dynamic"):
            if pat in name or pat in meta.lower():
                key = pat
                break
        buckets[key] += cyc
    out = {
        "tag": tag,
        "est_total_Mcycles": round(total_cyc / 1e6, 1),
        "by_bucket_Mcycles": {k: round(v / 1e6, 1)
                              for k, v in buckets.most_common()},
        "top12": [(round(c / 1e6, 2), n, m) for c, n, m in rows[:12]],
    }

    o = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    float(np.asarray(o[0]).ravel()[0])
    best = None
    for _ in range(3):
        t0 = time.time()
        fetched = []
        for _ in range(10):
            o = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
            fetched.append(o[0])
        float(np.asarray(fetched[-1]).ravel()[0])
        dt = (time.time() - t0) / 10
        best = dt if best is None else min(best, dt)
    out["step_ms"] = round(best * 1e3, 2)
    ca = ex.cost_analysis()
    out["bytes_GB"] = round(float(ca.get("bytes accessed", 0)) / 1e9, 3)
    out["flops_G"] = round(float(ca.get("flops", 0)) / 1e9, 1)
    print(json.dumps(out), flush=True)


def main():
    analyze("sparse_pad128", True, row_pad=128)
    analyze("dense_pad128", False, row_pad=128)


if __name__ == "__main__":
    main()
