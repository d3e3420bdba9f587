"""Probe: flash fwd+bwd at T=8k/16k/32k across block configs, with the
causal block-skip landed. Interleaved rounds per T.

    python tools/probe_flash_blocks.py
"""
import json
import sys
import time

import numpy as np


def _realize(x):
    return float(np.asarray(x).ravel()[0])


def _attn_flops(b, h, t, d):
    return 3.5 * (2 * 2 * b * h * t * t * d) * 0.5


def _runner(T, bq, bk, b=1, h=8, d=128, reps=3):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(0)
    shape = (b, h, T, d)
    q = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype=jnp.bfloat16)

    def loss(q, k, v):
        out = pk.flash_attention(q, k, v, causal=True, block_q=bq,
                                 block_k=bk)
        return jnp.sum(out.astype(jnp.float32))

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    try:
        out = g(q, k, v)
        _realize(out[0][0, 0, 0, 0])
    except Exception as e:
        return None, f"failed: {type(e).__name__}: {e!s:.80}"

    def run():
        t0 = time.time()
        for _ in range(reps):
            out = g(q, k, v)
        _realize(out[0][0, 0, 0, 0])
        return (time.time() - t0) / reps
    return run, None


def main():
    configs = [(512, 1024), (1024, 1024), (1024, 2048), (2048, 1024),
               (512, 2048)]
    for T in (8192, 16384, 32768):
        runners = {}
        for bq, bk in configs:
            r, err = _runner(T, bq, bk)
            if r is None:
                print(json.dumps({"T": T, "cfg": [bq, bk], "err": err}),
                      flush=True)
            else:
                runners[(bq, bk)] = r
        best = {c: None for c in runners}
        for _ in range(3):
            for c, r in runners.items():
                dt = r()
                best[c] = dt if best[c] is None else min(best[c], dt)
        fl = _attn_flops(1, 8, T, 128)
        print(json.dumps({
            "T": T,
            "results": {f"{c[0]}x{c[1]}":
                        {"ms": round(v * 1e3, 2),
                         "attn_tflops": round(fl / v / 1e12, 1)}
                        for c, v in best.items()},
        }), flush=True)


if __name__ == "__main__":
    main()
