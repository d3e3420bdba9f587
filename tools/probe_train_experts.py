"""Probe: one routed TRAINING layer (`fusion/moe.py` `train_experts`) timed
alone on the chip, forward and backward, with the device's own split of a
call: the grouped products and every pass around them.

    python tools/probe_train_experts.py                      # the 8k cell's shape
    python tools/probe_train_experts.py --held 16384,47000,58000 --top 16
    python tools/probe_train_experts.py --backend pallas_interpret \\
        --shape 256,4,256,128,4,8 --held 300 --reps 1 --trace 0   # a CPU rehearsal

`--shape` is N,k,D,F,held experts,routed experts (the cell: 8,192 rows under
top-8 of 64, 16 held, experts of [2304, 896]); `--held` the pairs that land
on a held expert, one reading each: 16,384 is an even routing's share (the
cell's first ten steps), 47,000 and 58,000 what its lone rank holds at the
end of a run (PERF.md section 6, PR 50). The routing is drawn to hold
exactly that many pairs, spread evenly over the held experts. One JSON line
a reading: `ms_fwd_bwd` (host clock around `--reps` calls of the jitted
value-and-gradients, best of `--rounds`), `buffers` (the pair buffers the
program holds), and from a profiler trace of `--reps` more calls `busy_ms`
(the device's busy time a call), `products_ms` (megablox's `gmm` / `tgmm`
calls, what `moe_train_experts_roofline` reads), `kernels_ms` (every Mosaic
call: the products and this repo's own), and `ops`, the `--top` device
operations by milliseconds a call under the ledger's names (an operation
inside a conditional counts in the conditional's line too). The file runs
unchanged from a checkout of an older commit copied beside it, which is how
a change is read against its parent in one call.
"""
import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def routing(rng, n, k, n_held, n_routed, held_pairs):
    """idx [n, k]: every row selects k distinct experts, `held_pairs` of the
    n x k selections among the first `n_held` ids, one held expert after
    another so that they hold the same count within one."""
    base, extra = divmod(held_pairs, n)
    assert base + (extra > 0) <= min(k, n_held), "more held pairs than fit"
    idx = np.empty((n, k), np.int32)
    turn = 0
    for row in range(n):
        mine = base + (row < extra)
        idx[row, :mine] = (turn + np.arange(mine)) % n_held
        turn += mine
        idx[row, mine:] = n_held + rng.choice(n_routed - n_held, k - mine,
                                              replace=False)
    return idx[rng.permutation(n)]


def reading(args, held_pairs):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.fusion import moe

    n, k, d, f, n_held, n_routed = (int(v) for v in args.shape.split(","))
    rng = np.random.RandomState(args.seed)
    idx = jnp.asarray(routing(rng, n, k, n_held, n_routed, held_pairs))
    w = jnp.asarray(rng.rand(n, k).astype(np.float32) / k)
    x, probe = (jnp.asarray(rng.randn(n, d).astype(np.float32))
                for _ in range(2))
    gate, up = (jnp.asarray(rng.randn(n_held, d, f).astype(np.float32)
                            * d ** -0.5) for _ in range(2))
    down = jnp.asarray(rng.randn(n_held, f, d).astype(np.float32) * f ** -0.5)
    held = tuple(range(n_held))

    def layer(x, w, gate, up, down):
        out, sizes = moe.train_experts(x, idx, w, held, n_routed, gate, up,
                                       down, backend=args.backend)
        return jnp.sum(out * probe), sizes

    step = jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True))
    operands = (x, w, gate, up, down)
    (_, sizes), grads = jax.block_until_ready(step(*operands))
    assert int(sizes.sum()) == held_pairs, (int(sizes.sum()), held_pairs)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)

    def run():
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = step(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.reps * 1e3

    line = {"held_pairs": held_pairs, "shape": args.shape,
            "backend": args.backend or "auto",
            "buffers": list(moe._pair_rows(n * k, n_held, n_routed))
            if hasattr(moe, "_pair_rows") else [-(-n * k // 512) * 512],
            "ms_fwd_bwd": round(min(run() for _ in range(args.rounds)), 3)}
    if args.trace:
        line.update(device_split(run, args))
    return line


def device_split(run, args):
    import jax

    from benchmark import xplane

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        run()
        jax.profiler.stop_trace()
        device = xplane.Trace(xplane.find_xplane(tmp)).devices[0]
    per_call = 1e3 / args.reps
    by_key, products, kernels = {}, 0.0, 0.0
    for start, end, key, _, mosaic in device.ops:
        by_key[key] = by_key.get(key, 0.0) + (end - start) * per_call
        if mosaic:
            kernels += (end - start) * per_call
            if "gmm" in key:
                products += (end - start) * per_call
    top = sorted(by_key.items(), key=lambda kv: -kv[1])[:args.top]
    return {"busy_ms": round(xplane.measure(device.busy()) * per_call, 3),
            "products_ms": round(products, 3),
            "kernels_ms": round(kernels, 3),
            "ops": {key: round(ms, 3) for key, ms in top}}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", default="8192,8,2304,896,16,64")
    ap.add_argument("--held", default="16384,47000,58000")
    ap.add_argument("--backend", default=None,
                    help="pallas_interpret rehearses on a CPU")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--top", type=int, default=14)
    args = ap.parse_args()
    for held_pairs in (int(v) for v in args.held.split(",")):
        print(json.dumps(reading(args, held_pairs)), flush=True)


if __name__ == "__main__":
    main()
