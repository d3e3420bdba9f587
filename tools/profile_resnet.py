"""ResNet-50 MFU attribution probes, consolidated (r12).

One flag-driven driver replacing the round-3/4 numbered copies
(profile_resnet{,2,3,4}.py), backed by the r12 observability API:
shape-byte parsing comes from `framework.costs.hlo_shape_bytes` (the one
copy), roofline verdicts from `framework.costs.roofline_fields`, and the
timed loops record "step" spans so the Chrome trace shows the same
intervals the JSON rows quote.

    python tools/profile_resnet.py --exp bench --batch_size 256
    python tools/profile_resnet.py --exp all

Experiments (--exp, repeatable):
  bench          pipelined step time + implied TFLOP/s (r02 baseline repro)
  overhead       per-call floor: identity over the same state pytree,
                 per-buffer vs per-byte split (one packed buffer)
  scan           K train steps fused into one lax.scan dispatch
  roofline       XLA cost-analysis bytes/flops -> HBM- vs MXU-bound verdict
  fwd_only       forward+loss only: is bwd disproportionately slow?
  conv_micro     stem 7x7/s2, space-to-depth variant, body 3x3 fwd+bwd
  hlo_bytes      per-opcode output-byte census of EVERY instruction line
  buffer_census  entry-computation-only census (real materialized buffers)
                 + biggest buffers with op_name metadata
"""

from __future__ import annotations

import argparse
import collections
import json
import re
import time

import numpy as np

EXPERIMENTS = ("bench", "overhead", "scan", "roofline", "fwd_only",
               "conv_micro", "hlo_bytes", "buffer_census")


def _realize(x):
    """Barrier: host-value realization."""
    return float(np.asarray(x).ravel()[0])


def _build_train(batch, rng):
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import models

    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss, acc, _ = models.resnet.resnet_imagenet(
            depth=50, is_test=False, data_format="NHWC", use_bf16=True)
        opt = pt.optimizer.MomentumOptimizer(learning_rate=3e-3,
                                             momentum=0.9)
        opt.minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = {
        "img": jnp.asarray(rng.rand(batch, 224, 224, 3).astype("float32")),
        "label": jnp.asarray(rng.randint(0, 1000,
                                         (batch, 1)).astype("int64")),
    }
    return exe, loss, feed


def _compiled_executable(exe, loss, feed):
    import jax.numpy as jnp
    import paddle_tpu as pt
    compiled = exe._lookup_or_compile(
        pt.default_main_program(), feed, [loss.name], pt.global_scope())
    scope = pt.global_scope()
    feed_vals = tuple(jnp.asarray(feed[n]) for n in compiled.feed_names)
    ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
    rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
    return compiled.fn.lower(feed_vals, ro_vals, rw_vals,
                             np.uint32(0)).compile()


def exp_bench(args, rng):
    from paddle_tpu.observability import tracing
    exe, loss, feed = _build_train(args.batch_size, rng)
    out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    _realize(out[0])
    t0 = time.time()
    fetched = []
    with tracing.span("user", f"profile_resnet/bench_bs{args.batch_size}"):
        for _ in range(args.iters):
            out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
            fetched.append(out[0])
        _realize(fetched[-1])
    dt = time.time() - t0
    ca = exe.cost_analysis(feed=feed, fetch_list=[loss])
    flops = float(ca.get("flops", 0.0)) if ca else 0.0
    print(json.dumps({
        "exp": f"resnet_bs{args.batch_size}",
        "step_ms": round(dt / args.iters * 1e3, 2),
        "imgs_per_sec": round(args.batch_size * args.iters / dt, 1),
        "flops_per_step": flops,
        "implied_tflops": round(flops * args.iters / dt / 1e12, 1),
    }), flush=True)
    return exe


def exp_overhead(args, rng):
    """Per-call floor: identity-ish update over the SAME state buffers the
    train step carries, with ~zero FLOPs; then the same bytes in ONE
    buffer (per-buffer vs per-byte overhead split)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt

    _build_train(args.batch_size, rng)
    scope = pt.global_scope()
    state = [scope.get(n) for n in sorted(scope.local_var_names())]
    state = [s for s in state if hasattr(s, "dtype")]
    n_buffers = len(state)
    n_bytes = int(sum(np.prod(s.shape) * s.dtype.itemsize for s in state))

    @jax.jit
    def ident(xs):
        return [x + jnp.ones((), x.dtype) for x in xs]

    out = ident(state)
    _realize(out[0])
    t0 = time.time()
    for _ in range(20):
        out = ident(out)
    _realize(out[0])
    print(json.dumps({
        "exp": "overhead_identity",
        "step_ms": round((time.time() - t0) / 20 * 1e3, 2),
        "n_buffers": n_buffers, "mbytes": round(n_bytes / 1e6, 1),
    }), flush=True)

    big = jnp.zeros(n_bytes // 4, jnp.float32)

    @jax.jit
    def ident1(x):
        return x + 1.0

    out = ident1(big)
    _realize(out)
    t0 = time.time()
    for _ in range(20):
        out = ident1(out)
    _realize(out)
    print(json.dumps({
        "exp": "overhead_packed",
        "step_ms": round((time.time() - t0) / 20 * 1e3, 2),
        "n_buffers": 1, "mbytes": round(n_bytes / 1e6, 1),
    }), flush=True)


def exp_scan(args, rng):
    """K train steps per XLA execution via lax.scan over stacked batches
    (uint8-staged images cast+scaled on device)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt

    batch, k = args.batch_size, args.scan_k
    exe, loss, _ = _build_train(batch, rng)
    prog, scope = pt.default_main_program(), pt.global_scope()
    compiled = exe._lookup_or_compile(
        prog,
        {"img": np.zeros((batch, 224, 224, 3), np.float32),
         "label": np.zeros((batch, 1), np.int64)},
        [loss.name], scope)

    imgs = jnp.asarray(rng.randint(
        0, 255, (k, batch, 224, 224, 3)).astype(np.uint8))
    labels = jnp.asarray(rng.randint(0, 1000, (k, batch, 1)).astype("int64"))
    ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
    rw0 = tuple(scope.get(n) for n in compiled.rw_names)
    rw_out_idx = [compiled.state_out_names.index(n)
                  for n in compiled.rw_names]

    def one(rw_vals, xs):
        img_u8, lab = xs
        img = img_u8.astype(jnp.float32) / 255.0
        fetches, new_state = compiled.fn.__wrapped__(
            (img, lab), ro_vals, rw_vals, np.uint32(1))
        return tuple(new_state[i] for i in rw_out_idx), fetches[0]

    @jax.jit
    def loop(rw_vals, imgs, labels):
        return jax.lax.scan(one, rw_vals, (imgs, labels))

    rw, losses = loop(rw0, imgs, labels)
    _realize(losses[-1])
    outer = 3
    t0 = time.time()
    for _ in range(outer):
        rw, losses = loop(rw, imgs, labels)
    _realize(losses[-1])
    dt = time.time() - t0
    print(json.dumps({
        "exp": f"resnet_scan{k}_bs{batch}",
        "step_ms": round(dt / (outer * k) * 1e3, 2),
        "imgs_per_sec": round(batch * k * outer / dt, 1),
        "loss_first": round(float(losses[0]), 3),
        "loss_last": round(float(losses[-1]), 3),
    }), flush=True)


def exp_roofline(args, rng):
    from paddle_tpu.framework.costs import roofline_fields
    exe, loss, feed = _build_train(args.batch_size, rng)
    ca = exe.cost_analysis(feed=feed, fetch_list=[loss])
    flops = float(ca.get("flops", 0.0))
    baw = float(ca.get("bytes accessed", 0.0))
    out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    _realize(out[0])
    t0 = time.time()
    for _ in range(args.iters):
        out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    _realize(out[0])
    step_s = (time.time() - t0) / args.iters
    print(json.dumps({
        "exp": "roofline_train_step",
        "bytes_accessed_output": float(
            ca.get("bytes accessed output", 0.0)),
        **roofline_fields(step_s, flops, baw),
    }), flush=True)


def exp_fwd_only(args, rng):
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import models

    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss, acc, _ = models.resnet.resnet_imagenet(
            depth=50, is_test=False, data_format="NHWC", use_bf16=True)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = {
        "img": jnp.asarray(rng.rand(args.batch_size, 224, 224,
                                    3).astype("float32")),
        "label": jnp.asarray(rng.randint(
            0, 1000, (args.batch_size, 1)).astype("int64")),
    }
    out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    _realize(out[0])
    t0 = time.time()
    for _ in range(args.iters):
        out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    _realize(out[0])
    dt = (time.time() - t0) / args.iters
    ca = exe.cost_analysis(feed=feed, fetch_list=[loss])
    f2 = float(ca.get("flops", 0.0))
    print(json.dumps({
        "exp": f"fwd_only_bs{args.batch_size}",
        "step_ms": round(dt * 1e3, 2), "flops": f2,
        "implied_tflops": round(f2 / dt / 1e12, 1),
    }), flush=True)


def _conv_micro(name, x_shape, k_shape, stride, padding):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.costs import V5E_PEAK_TFLOPS

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*x_shape).astype(np.float32), jnp.bfloat16)
    k = jnp.asarray(rng.randn(*k_shape).astype(np.float32), jnp.bfloat16)

    def f(x, k):
        out = jax.lax.conv_general_dilated(
            x, k, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum(out.astype(jnp.float32))

    g = jax.jit(jax.grad(f, argnums=(0, 1)))
    out = g(x, k)
    _realize(out[0])
    t0 = time.time()
    for _ in range(10):
        out = g(x, k)
    _realize(out[0])
    dt = (time.time() - t0) / 10
    n, h, w, _ = x_shape
    kh, kw, ci, co = k_shape
    oh = (h + sum(padding[0]) - kh) // stride + 1
    ow = (w + sum(padding[1]) - kw) // stride + 1
    flops = 3 * 2 * n * oh * ow * kh * kw * ci * co  # fwd + 2 bwd convs
    print(json.dumps({
        "exp": name, "ms": round(dt * 1e3, 2),
        "tflops_attained": round(flops / dt / 1e12, 1),
        "pct_peak": round(flops / dt / V5E_PEAK_TFLOPS / 10.0, 1),
    }), flush=True)


def exp_conv_micro(args, rng):
    b = args.batch_size
    _conv_micro("stem_conv7x7s2_c3", (b, 224, 224, 3), (7, 7, 3, 64), 2,
                ((3, 3), (3, 3)))
    _conv_micro("stem_s2d_conv4x4s1_c12", (b, 112, 112, 12),
                (4, 4, 12, 64), 1, ((1, 2), (1, 2)))
    _conv_micro("body_conv3x3_c128", (b, 28, 28, 128), (3, 3, 128, 128), 1,
                ((1, 1), (1, 1)))
    _conv_micro("body_conv3x3_c256_14", (b, 14, 14, 256),
                (3, 3, 256, 256), 1, ((1, 1), (1, 1)))


def _dump_hlo(args, rng):
    exe, loss, feed = _build_train(args.batch_size, rng)
    ex = _compiled_executable(exe, loss, feed)
    hlo = ex.as_text()
    with open("/tmp/resnet_train_optimized.hlo", "w") as f:
        f.write(hlo)
    return hlo, ex


def exp_hlo_bytes(args, rng):
    """Per-opcode output-byte census over EVERY instruction line (includes
    fusion-internal lines that never touch HBM — see buffer_census for the
    materialized-only view)."""
    from paddle_tpu.framework.costs import hlo_shape_bytes
    hlo, ex = _dump_hlo(args, rng)
    op_bytes = collections.Counter()
    op_count = collections.Counter()
    big_f32 = []
    for line in hlo.splitlines():
        m = re.search(r"=\s+([a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z\-]+)", line)
        if not m:
            continue
        sh, op = m.group(1), m.group(2)
        try:
            b = hlo_shape_bytes(sh)
        except ValueError:
            continue
        op_bytes[op] += b
        op_count[op] += 1
        if sh.startswith("f32") and b > 50e6:
            big_f32.append((round(b / 1e6), op, line.strip()[:140]))
    print(json.dumps({
        "exp": "hlo_output_bytes_by_op",
        "top": [(op, round(b / 1e9, 2), op_count[op])
                for op, b in op_bytes.most_common(15)],
    }), flush=True)
    big_f32.sort(reverse=True)
    print(json.dumps({"exp": "big_f32_buffers",
                      "top10": big_f32[:10]}), flush=True)
    ca = ex.cost_analysis()
    ca = ca or {}
    keys = {k: v for k, v in ca.items()
            if "bytes" in k and isinstance(v, float) and v > 1e9}
    print(json.dumps({"exp": "cost_analysis_byte_keys", "keys": keys}),
          flush=True)


def exp_buffer_census(args, rng):
    """Entry-computation-only census: top-level instructions of the
    compiled module — the ones whose outputs are real HBM buffers —
    bucketed by opcode and dtype, plus the biggest buffers w/ metadata."""
    from paddle_tpu.framework.costs import hlo_shape_bytes
    hlo, ex = _dump_hlo(args, rng)
    cur_comp = None
    entry_ops = []
    for line in hlo.splitlines():
        mc = re.match(r"(ENTRY )?%?([\w.\-]+)\s*\([^)]*\)\s*->", line)
        if mc:
            cur_comp = ("ENTRY" if mc.group(1) else mc.group(2))
            continue
        if cur_comp != "ENTRY":
            continue
        m = re.match(r"\s+%?([\w.\-]+)\s*=\s*(\S+)\s+([a-z\-]+)", line)
        if not m:
            continue
        name, sh, op = m.groups()
        try:
            b = hlo_shape_bytes(sh)
        except ValueError:
            b = 0
        mm = re.search(r'op_name="([^"]*)"', line)
        entry_ops.append((b, op, sh, name, mm.group(1) if mm else ""))

    op_bytes = collections.Counter()
    op_count = collections.Counter()
    dtype_bytes = collections.Counter()
    for b, op, sh, name, meta in entry_ops:
        op_bytes[op] += b
        op_count[op] += 1
        md = re.match(r"([a-z0-9]+)\[", sh)
        if md:
            dtype_bytes[md.group(1)] += b
    print(json.dumps({
        "exp": "entry_output_bytes_by_op",
        "total_GB": round(sum(op_bytes.values()) / 1e9, 2),
        "top": [(op, round(bb / 1e9, 2), op_count[op])
                for op, bb in op_bytes.most_common(18)],
        "by_dtype_GB": {d: round(bb / 1e9, 2)
                        for d, bb in dtype_bytes.most_common()},
    }), flush=True)
    big = sorted(entry_ops, reverse=True)[:20]
    print(json.dumps({
        "exp": "biggest_entry_buffers",
        "top20": [(round(b / 1e6), op, sh[:48], meta[:90])
                  for b, op, sh, name, meta in big],
    }), flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--exp", action="append", choices=EXPERIMENTS + ("all",),
                   help="experiment(s) to run; default bench")
    p.add_argument("--batch_size", type=int, default=256)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--scan_k", type=int, default=8,
                   help="scan: train steps fused per dispatch")
    args = p.parse_args()
    exps = args.exp or ["bench"]
    if "all" in exps:
        exps = list(EXPERIMENTS)

    import jax
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}),
          flush=True)
    rng = np.random.RandomState(0)
    fns = {"bench": exp_bench, "overhead": exp_overhead, "scan": exp_scan,
           "roofline": exp_roofline, "fwd_only": exp_fwd_only,
           "conv_micro": exp_conv_micro, "hlo_bytes": exp_hlo_bytes,
           "buffer_census": exp_buffer_census}
    for e in exps:
        fns[e](args, np.random.RandomState(0) if e != "bench" else rng)


if __name__ == "__main__":
    main()
