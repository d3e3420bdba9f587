"""Conv-backward emitter probes, consolidated (r12).

One flag-driven driver replacing the four numbered copies
(probe_dgrad{,2,3,4}.py), which were successive METHODOLOGY refinements
of one question (VERDICT r4 #1: is the conv dgrad's HBM excess
program-reducible?). The timing modes preserve that lineage:

  --timing simple        one arg-tuple, best-of-windows (the original
                         probe_dgrad; KNOWN to overstate identical-call
                         throughput — kept for methodology A/Bs)
  --timing interleaved   4 distinct input variants cycled per iteration
                         (probe_dgrad2's fix for the CSE artifact)
  --timing scan          32 reps inside one jit via a rolled lax.scan —
                         per-dispatch overhead amortized
                         (probe_dgrad3's final form)

Experiments (--exp, repeatable):
  dgrad_1x1     isolated 1x1 dgrad: conv emitter vs one dot_general
  vjp_1x1       full fwd+bwd vjp of the 1x1 conv: all-conv vs all-dot
  dgrad_3x3     3x3 dgrad: conv emitter vs im2col+dot
  mixed_1x1     custom_vjp with conv fwd + dot dgrad + conv wgrad — each
                half on its winning emitter (probe_dgrad4's decider; the
                PTPU_CONV1X1_MIXED_VJP flag ships this lowering)

    python tools/probe_dgrad.py --exp dgrad_1x1 --timing scan
    python tools/probe_dgrad.py --exp all --timing interleaved
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

DN = ("NHWC", "HWIO", "NHWC")
NVAR = 4           # distinct input variants (interleaved mode)
REPS = 32          # scan length inside one dispatch (scan mode)
B, HW, Ci, Co = 256, 56, 256, 64
C3 = 64

EXPERIMENTS = ("dgrad_1x1", "vjp_1x1", "dgrad_3x3", "mixed_1x1")


def _sync(out):
    """Barrier by host-value realization: fetch one scalar element of the
    final output — 4 bytes over the link, ordered after the whole queue."""
    x = out
    while isinstance(x, (tuple, list)):
        x = x[0]
    return float(np.asarray(x[(0,) * x.ndim] if x.ndim else x))


def _time_simple(fn, variants, iters, windows):
    _sync(fn(*variants[0]))
    best = None
    for _ in range(windows):
        t0 = time.time()
        out = None
        for _ in range(iters):
            out = fn(*variants[0])
        _sync(out)
        dt = (time.time() - t0) / iters
        best = dt if best is None else min(best, dt)
    return best


def _time_interleaved(fn, variants, iters, windows):
    for v in variants:
        _sync(fn(*v))
    best = None
    for _ in range(windows):
        t0 = time.time()
        out = None
        for i in range(iters):
            out = fn(*variants[i % len(variants)])
        _sync(out)
        dt = (time.time() - t0) / iters
        best = dt if best is None else min(best, dt)
    return best


def _time_scan(op, variants, iters, windows):
    """REPS executions inside ONE jit dispatch via a rolled lax.scan; the
    carry folds into the first operand (+ carry*0, unfoldable for floats)
    so nothing hoists or CSEs."""
    args = variants[0]

    @jax.jit
    def f():
        def body(carry, _):
            a0 = args[0] + carry.astype(args[0].dtype) * 0
            out = op(a0, *args[1:])
            while isinstance(out, (tuple, list)):
                out = out[0]
            return carry + out.reshape(-1)[0].astype(jnp.float32), None
        carry, _ = jax.lax.scan(body, jnp.float32(0), None, length=REPS)
        return carry

    float(np.asarray(f()))
    best = None
    for _ in range(windows):
        t0 = time.time()
        float(np.asarray(f()))
        dt = (time.time() - t0) / REPS
        best = dt if best is None else min(best, dt)
    return best


TIMING = {"simple": _time_simple, "interleaved": _time_interleaved,
          "scan": _time_scan}


def _cost(fn, args):
    ex = jax.jit(fn).lower(*args).compile()
    ca = ex.cost_analysis()
    ca = ca or {}
    return (float(ca.get("bytes accessed", 0.0)),
            float(ca.get("flops", 0.0)))


def _report(name, fn, variants, args):
    timer = TIMING[args.timing]
    jfn = fn if args.timing == "scan" else jax.jit(fn)
    t = timer(jfn, variants, args.iters, args.windows)
    b, f = _cost(fn, variants[0])
    row = {"variant": name, "timing": args.timing,
           "ms": round(t * 1e3, 3),
           "bytes_MB": round(b / 1e6, 1), "flops_G": round(f / 1e9, 2),
           "achieved_GBps": round(b / t / 1e9, 1) if b else None,
           "achieved_TFLOPs": round(f / t / 1e12, 2) if f else None,
           "n_distinct_inputs": (len(variants)
                                 if args.timing == "interleaved" else 1)}
    print(json.dumps(row), flush=True)
    return row


def conv_fwd(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=DN)


def _mk(rng, shape):
    return [jnp.asarray(rng.rand(*shape).astype("float32"), jnp.bfloat16)
            for _ in range(NVAR)]


def exp_dgrad_1x1(args, rng, results):
    dys, ws, xs = (_mk(rng, (B, HW, HW, Co)), _mk(rng, (1, 1, Ci, Co)),
                   _mk(rng, (B, HW, HW, Ci)))

    def dgrad_conv(dy, w, x):
        _, vjp = jax.vjp(lambda x_: conv_fwd(x_, w), x)
        return vjp(dy)[0]

    def dgrad_dot(dy, w, x):
        dy2 = dy.reshape(-1, Co)
        dx = jax.lax.dot_general(dy2, w.reshape(Ci, Co),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return dx.astype(dy.dtype).reshape(B, HW, HW, Ci)

    print("== dgrad_1x1 [256,56,56,64] -> [256,56,56,256]", flush=True)
    var3 = list(zip(dys, ws, xs))
    a = _report("dgrad_1x1_conv_emitter", dgrad_conv, var3, args)
    b = _report("dgrad_1x1_dot_general", dgrad_dot, var3, args)
    np.testing.assert_allclose(
        np.asarray(dgrad_conv(*var3[0]), np.float32),
        np.asarray(dgrad_dot(*var3[0]), np.float32), rtol=2e-2, atol=1e-2)
    results["dgrad_1x1_speedup_dot_over_conv"] = round(a["ms"] / b["ms"], 3)


def exp_vjp_1x1(args, rng, results):
    xs, ws, dys = (_mk(rng, (B, HW, HW, Ci)), _mk(rng, (1, 1, Ci, Co)),
                   _mk(rng, (B, HW, HW, Co)))

    def vjp_conv(x, w, dy):
        y, vjp = jax.vjp(lambda x_, w_: conv_fwd(x_, w_), x, w)
        return (y,) + vjp(dy)

    def vjp_dot(x, w, dy):
        x2, w2, dy2 = x.reshape(-1, Ci), w.reshape(Ci, Co), dy.reshape(-1,
                                                                       Co)

        def f(x2_, w2_):
            return jax.lax.dot_general(
                x2_, w2_, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(x2_.dtype)
        y2, vjp = jax.vjp(f, x2, w2)
        dx2, dw2 = vjp(dy2)
        return (y2.reshape(B, HW, HW, Co), dx2.reshape(B, HW, HW, Ci),
                dw2.reshape(1, 1, Ci, Co))

    print("== vjp_1x1 fwd+bwd", flush=True)
    var = list(zip(xs, ws, dys))
    a = _report("vjp_1x1_conv_emitter", vjp_conv, var, args)
    b = _report("vjp_1x1_dot_general", vjp_dot, var, args)
    results["vjp_1x1_speedup_dot_over_conv"] = round(a["ms"] / b["ms"], 3)


def exp_dgrad_3x3(args, rng, results):
    dys, ws, xs = (_mk(rng, (B, HW, HW, C3)), _mk(rng, (3, 3, C3, C3)),
                   _mk(rng, (B, HW, HW, C3)))

    def dgrad_conv(dy, w, x):
        _, vjp = jax.vjp(lambda x_: conv_fwd(x_, w), x)
        return vjp(dy)[0]

    def dgrad_im2col(dy, w, x):
        # dx = full-correlation of dy with the spatially-flipped filter:
        # extract 3x3 patches of dy -> [B,H,W,9*C] then one dot with the
        # flipped filter reshaped [9*C, C]. Same math, matmul emitter.
        patches = jax.lax.conv_general_dilated_patches(
            dy, (3, 3), (1, 1), "SAME", dimension_numbers=DN)
        wf = jnp.flip(w, (0, 1))
        wr = jnp.transpose(wf, (3, 0, 1, 2)).reshape(9 * C3, C3)
        dx = jax.lax.dot_general(
            patches.reshape(-1, 9 * C3), wr, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dx.astype(dy.dtype).reshape(B, HW, HW, C3)

    print("== dgrad_3x3 64ch @56x56", flush=True)
    var = list(zip(dys, ws, xs))
    a = _report("dgrad_3x3_conv_emitter", dgrad_conv, var, args)
    b = _report("dgrad_3x3_im2col_dot", dgrad_im2col, var, args)
    np.testing.assert_allclose(
        np.asarray(dgrad_conv(*var[0]), np.float32),
        np.asarray(dgrad_im2col(*var[0]), np.float32),
        rtol=3e-2, atol=3e-1)
    results["dgrad_3x3_speedup_im2col_over_conv"] = round(
        a["ms"] / b["ms"], 3)


def exp_mixed_1x1(args, rng, results):
    """conv fwd + dot dgrad + conv wgrad via custom_vjp: each half routed
    to the emitter that won its isolated probe."""
    @jax.custom_vjp
    def conv1x1_mixed(x, w):
        return conv_fwd(x, w)

    def _fwd(x, w):
        return conv_fwd(x, w), (x, w)

    def _bwd(res, dy):
        x, w = res
        dy2 = dy.reshape(-1, Co)
        dx = jax.lax.dot_general(
            dy2, w.reshape(Ci, Co), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dy.dtype)
        dx = dx.reshape(B, HW, HW, Ci)
        _, vjp = jax.vjp(lambda w_: conv_fwd(x, w_), w)
        return dx, vjp(dy)[0]

    conv1x1_mixed.defvjp(_fwd, _bwd)

    xs, ws = _mk(rng, (B, HW, HW, Ci)), _mk(rng, (1, 1, Ci, Co))
    dys = [jnp.asarray(rng.rand(B, HW, HW, Co).astype("float32"))
           for _ in range(NVAR)]

    def mk_loss(fn):
        def run(x, w, dy):
            def loss(x_, w_):
                return jnp.sum(fn(x_, w_).astype(jnp.float32) * dy)
            v, g = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
            return g[0]
        return run

    # parity first
    g1 = jax.grad(lambda x_: jnp.sum(conv_fwd(x_, ws[0])
                                     .astype(jnp.float32) * dys[0]))(xs[0])
    g2 = jax.grad(lambda x_: jnp.sum(conv1x1_mixed(x_, ws[0])
                                     .astype(jnp.float32) * dys[0]))(xs[0])
    np.testing.assert_allclose(np.asarray(g1, np.float32),
                               np.asarray(g2, np.float32),
                               rtol=2e-2, atol=2e-1)
    print("== mixed_1x1 fwd+bwd (conv fwd / dot dgrad / conv wgrad)",
          flush=True)
    var = list(zip(xs, ws, dys))
    a = _report("vjp_1x1_all_conv", mk_loss(conv_fwd), var, args)
    b = _report("vjp_1x1_mixed_emitter", mk_loss(conv1x1_mixed), var, args)
    results["mixed_1x1_speedup_over_conv"] = round(a["ms"] / b["ms"], 3)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--exp", action="append", choices=EXPERIMENTS + ("all",),
                   help="experiment(s); default dgrad_1x1")
    p.add_argument("--timing", choices=sorted(TIMING), default="interleaved")
    p.add_argument("--iters", type=int, default=24)
    p.add_argument("--windows", type=int, default=4)
    args = p.parse_args()
    exps = args.exp or ["dgrad_1x1"]
    if "all" in exps:
        exps = list(EXPERIMENTS)

    print(json.dumps({"devices": [str(d) for d in jax.devices()],
                      "timing": args.timing}), flush=True)
    rng = np.random.RandomState(0)
    results = {}
    fns = {"dgrad_1x1": exp_dgrad_1x1, "vjp_1x1": exp_vjp_1x1,
           "dgrad_3x3": exp_dgrad_3x3, "mixed_1x1": exp_mixed_1x1}
    for e in exps:
        fns[e](args, rng, results)
    print(json.dumps({"exp": "dgrad_probe_summary", **results}), flush=True)


if __name__ == "__main__":
    main()
