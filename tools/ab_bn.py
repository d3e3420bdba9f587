"""A/B: legacy (autodiff-through-stats) BN vs the round-4 custom-VJP BN.

Builds the ResNet-50 bs256 train step twice — once with the legacy
batch_norm lowering monkeypatched in, once with the current one — and
reports, for each: XLA cost-analysis bytes/flops, materialized entry-buffer
census (by dtype), and interleaved best-of-N step timing (see
bench.interleaved_best).

    python tools/ab_bn.py
"""

from __future__ import annotations

import json
import re
import time

import numpy as np


def legacy_batch_norm(ctx, ins, attrs):
    """Round-3 final _batch_norm: fma apply, but stats differentiated by
    autodiff (the path whose fp32 residuals VERDICT r3 #1 flagged)."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    data_layout = attrs.get("data_layout", "NCHW")
    is_test = attrs.get("is_test", False) or ctx.is_test
    axis = 1 if data_layout == "NCHW" else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
    else:
        shift_v = jax.lax.stop_gradient(mean)
        x32 = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
        xs_ = x32 - shift_v.reshape(bshape)
        m1s = jnp.mean(xs_, axis=reduce_axes)
        m2s = jnp.mean(jnp.square(xs_), axis=reduce_axes)
        use_mean = m1s + shift_v
        use_var = jnp.maximum(m2s - jnp.square(m1s), 0.0)
        m_d = jax.lax.stop_gradient(use_mean)
        v_d = jax.lax.stop_gradient(use_var)
        mean_out = momentum * mean + (1 - momentum) * m_d
        var_out = momentum * var + (1 - momentum) * v_d
    inv = jax.lax.rsqrt(use_var + eps)
    a32 = inv * scale
    b32 = bias - use_mean * a32
    y = x * a32.astype(x.dtype).reshape(bshape) \
        + b32.astype(x.dtype).reshape(bshape)
    return {"Y": [y], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [use_mean], "SavedVariance": [inv]}


def build(batch=256):
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import models

    pt.reset_default_programs()
    pt.reset_global_scope()
    loss, acc, _ = models.resnet.resnet_imagenet(
        depth=50, is_test=False, data_format="NHWC", use_bf16=True)
    opt = pt.optimizer.MomentumOptimizer(learning_rate=3e-3, momentum=0.9)
    opt.minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {
        "img": jnp.asarray(rng.rand(batch, 224, 224, 3).astype("float32")),
        "label": jnp.asarray(rng.randint(0, 1000, (batch, 1)).astype("int64")),
    }
    return exe, loss, feed


def census(hlo):
    it = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8}
    cur = None
    out = {"bf16": 0, "f32": 0}
    for line in hlo.splitlines():
        mc = re.match(r"(ENTRY )?%?([\w.\-]+)\s*\([^)]*\)\s*->", line)
        if mc:
            cur = "ENTRY" if mc.group(1) else mc.group(2)
            continue
        if cur != "ENTRY":
            continue
        m = re.match(r"\s+%?[\w.\-]+\s*=\s*(bf16|f32)\[([0-9,]*)\]", line)
        if not m or "get-tuple-element" in line or "parameter" in line \
                or "bitcast" in line:
            continue
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        out[m.group(1)] += n * it[m.group(1)]
    return {k: round(v / 1e9, 2) for k, v in out.items()}


def prepare(tag, batch=256, iters=10):
    import paddle_tpu as pt

    exe, loss, feed = build(batch)
    # capture program+scope: the NEXT prepare() resets the global defaults,
    # so the timing closures must not re-resolve them
    prog = pt.default_main_program()
    scope = pt.global_scope()
    compiled = exe._lookup_or_compile(prog, feed, [loss.name], scope)
    import jax.numpy as jnp
    feed_vals = tuple(jnp.asarray(feed[n]) for n in compiled.feed_names)
    scope = pt.global_scope()
    ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
    rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
    ex = compiled.fn.lower(feed_vals, ro_vals, rw_vals,
                           np.uint32(0)).compile()
    ca = ex.cost_analysis()
    stat = {
        "bytes_accessed_GB": round(float(ca.get("bytes accessed", 0)) / 1e9,
                                   2),
        "flops_G": round(float(ca.get("flops", 0)) / 1e9, 1),
        "entry_buffers_GB": census(ex.as_text()),
    }

    out = exe.run(program=prog, feed=feed, fetch_list=[loss],
                  scope=scope, return_numpy=False)
    float(out[0])

    def run():
        t0 = time.time()
        fetched = []
        for _ in range(iters):
            o = exe.run(program=prog, feed=feed, fetch_list=[loss],
                        scope=scope, return_numpy=False)
            fetched.append(o[0])
        float(fetched[-1])
        return (time.time() - t0) / iters

    print(json.dumps({"tag": tag, **stat}), flush=True)
    return run


def main():
    from paddle_tpu.framework import registry
    from paddle_tpu.ops import nn_ops

    run_new = prepare("new_custom_vjp")
    saved = registry._OPS["batch_norm"]
    registry._OPS["batch_norm"] = registry.OpDef(
        "batch_norm", legacy_batch_norm)
    try:
        run_legacy = prepare("legacy_autodiff_stats")
    finally:
        registry._OPS["batch_norm"] = saved

    best = {"new": None, "legacy": None}
    for _ in range(3):
        for name, run in (("new", run_new), ("legacy", run_legacy)):
            dt = run()
            best[name] = dt if best[name] is None else min(best[name], dt)
    print(json.dumps({
        "step_ms_new": round(best["new"] * 1e3, 1),
        "step_ms_legacy": round(best["legacy"] * 1e3, 1),
        "speedup_new_over_legacy": round(best["legacy"] / best["new"], 3),
    }), flush=True)


if __name__ == "__main__":
    main()
