"""Probe: the flash forward and backward timed alone on the chip, in either
operand layout and at any tiling.

    python tools/probe_flash_blocks.py --layout token_major --shape 8,16,1024,64 --causal 1
    python tools/probe_flash_blocks.py --layout head_major --shape 64,16,128,64 --causal 0
    python tools/probe_flash_blocks.py --layout head_major --shape 1,8,8192,128 \\
        --blocks 512x1024,1024x1024,1024x2048

`--shape` is B,H,T,D (`--tk` for keys of another length). Both layouts start
from q, k, v as the projections leave them, [B, T, H*D], and end in a
context of that shape, as a training step does: `head_major` is the call on
[B, H, T, D] between its four transposes (every call before PR 47, and a
rank-4 caller's since), `token_major` the op on the operands as they lie
(`_attend`; a shape the rule sends back to head-major says so
in `plan`). `--calls` of them are chained in one jitted forward and
backward, so that a launch's cost is paid once. One JSON line a
configuration: the best of `--rounds` rounds, milliseconds a call (forward
and backward), and the kernels' names. `--blocks` names tile sides
(block_q x block_k) to sweep, head-major only; without it the plan's own.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _runner(args, layout, blocks):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    B, H, T, D = (int(x) for x in args.shape.split(","))
    Tk = args.tk or T
    causal, scale = bool(args.causal), D ** -0.5
    rng = np.random.RandomState(0)

    def operand(t):
        return jnp.asarray(rng.randn(B, t, H * D).astype(np.float32),
                           dtype=jnp.bfloat16)

    q, k, v = operand(T), operand(Tk), operand(Tk)
    bq, bk = blocks or (None, None)

    def heads(x):
        return jnp.swapaxes(x.reshape(B, -1, H, D), 1, 2)

    def attend(x, k, v):
        if layout == "token_major":
            return pk._attend(x, k, v, None, scale, causal, args.backend,
                              H)
        out = pk.flash_attention(heads(x), heads(k), heads(v), scale=scale,
                                 causal=causal, block_q=bq, block_k=bk,
                                 backend=args.backend)
        return jnp.swapaxes(out, 1, 2).reshape(B, T, H * D)

    def loss(q, k, v):
        x = q
        for _ in range(args.calls):
            x = attend(x, k, v)
        return jnp.sum(x.astype(jnp.float32))

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    plan = None
    if hasattr(pk, "_plan_for"):
        if layout == "token_major":
            plan = pk._plan_for(q, k, False, num_heads=H)
        else:
            plan = pk._plan_for(heads(q), heads(k), False, bq, bk)
    jax.block_until_ready(g(q, k, v))

    def run():
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = g(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.reps / args.calls
    return run, plan


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--layout", choices=("head_major", "token_major"),
                    default="head_major")
    ap.add_argument("--shape", default="8,16,1024,64")
    ap.add_argument("--tk", type=int, default=0)
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--blocks", default="")
    ap.add_argument("--backend", default="pallas",
                    help="pallas_interpret rehearses on a CPU")
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()
    sweep = [tuple(int(x) for x in b.split("x"))
             for b in args.blocks.split(",") if b] or [None]
    if args.layout == "token_major" and sweep != [None]:
        ap.error("--blocks sweeps the head-major call only")
    runners = {}
    for blocks in sweep:
        try:
            runners[blocks] = _runner(args, args.layout, blocks)
        except Exception as e:      # a tiling Mosaic refuses is a finding
            print(json.dumps({"layout": args.layout, "blocks": blocks,
                              "err": f"{type(e).__name__}: {e!s:.200}"}),
                  flush=True)
    best = {b: None for b in runners}
    for _ in range(args.rounds):    # interleaved, so drift hits all alike
        for b, (run, _) in runners.items():
            dt = run()
            best[b] = dt if best[b] is None else min(best[b], dt)
    for b, (_, plan) in runners.items():
        print(json.dumps({
            "layout": args.layout, "shape": args.shape, "tk": args.tk,
            "causal": bool(args.causal), "blocks": b,
            "plan": repr(plan), "kernels": plan.scopes() if plan else None,
            "ms_per_call_fwd_bwd": round(best[b] * 1e3, 4)}), flush=True)


if __name__ == "__main__":
    main()
