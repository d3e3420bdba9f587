"""The state-space path of ONE layer of a two-mixer model, timed ALONE on the
chip (PR 54):

    chiprun --chips 1 -- python3 tools/probe_hybrid_layer.py [--slots 16]
        [--lanes 2] [--chunk 128] [--heads 32,128,2,256] [--reps 30]

At the shapes of `falcon-h1-34b-pp12_serve_long_prompts` (16 slots, 2 lanes
of 128 rows, 32 heads of 128 x 256 over 2 groups, bfloat16 rows, a float32
state):

- `ssd_chunk`: the lanes' chunked scan (`fusion/ssm.py`, plain XLA products
  in float32 at HIGHEST), one call a layer of a mixed tick;
- `ssm_decode_update` at 1, 4, 8 and 16 live rows: the decode rows' in-place
  state update (one Pallas call a layer of every tick);
- `ssm_scan`: the whole op a layer of a mixed tick runs (both of the above,
  the convolutions, the lanes' state picked and put back, a snapshot
  written), the state arrays donated as a tick donates them.

Each is timed as 100 calls chained through their state inside ONE launch (a
launch's own latency, 0.8 ms on the chip's host, is more than any of them).
One JSON line: the median milliseconds of a call of each, the bytes and
operations it cannot avoid, and its share of that roofline on the device's
peaks (benchmark/peaks.json). Read beside a traced run's mixed tick
(`tick_device_ms_p50` over the layers) it says what share of a layer's tick
the scan is: whether the chunked form wants a kernel of its own is decided
from these numbers (ROADMAP.md R3)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


INNER = 100     # calls inside one launch: a launch's own latency (0.8 ms
                # on the chip's host, more than these calls) is spread thin


def _median_ms(step, state, reps):
    """Median milliseconds of ONE call of `step(state) -> state`, timed as
    `INNER` calls chained through their state inside one jitted loop (each
    call reads what the call before it wrote, so none is elided), the wall
    clock around the launch run to its end, over `INNER`."""
    import jax
    loop = jax.jit(lambda st: jax.lax.fori_loop(
        0, INNER, lambda _, s: step(s), st), donate_argnums=0)
    times = []
    for k in range(reps + 2):
        t = time.perf_counter()
        state = jax.block_until_ready(loop(state))
        if k >= 2:
            times.append(1e3 * (time.perf_counter() - t) / INNER)
    return float(np.median(times))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--lanes", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--heads", default="32,128,2,256")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import ssm

    H, P, G, N = (int(v) for v in args.heads.split(","))
    S, L, C, K = args.slots, args.lanes, args.chunk, 4
    cd = H * P + 2 * G * N
    dev = jax.devices()[0]
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f).get(dev.device_kind)
    rng = np.random.default_rng(0)
    f32, bf16 = jnp.float32, jnp.bfloat16
    arr = lambda shape, dtype=f32, scale=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, dtype)
    a = -jnp.asarray(rng.uniform(1, 16, (H,)), f32)

    def share(ms, flops, nbytes):
        if not peaks:
            return None
        least = max(flops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
        return 100.0 * least / (ms / 1e3)

    out = {"device": f"{dev.platform} {dev.device_kind}",
           "shapes": {"slots": S, "lanes": L, "chunk": C, "heads": H,
                      "head_dim": P, "groups": G, "state": N}}

    # the lanes' chunked scan: each call starts from the state the call
    # before it left (scaled down: the state neither grows nor is elided)
    x, b, c = (arr((L, C, H, P), bf16), arr((L, C, G, N), bf16, 0.3),
               arr((L, C, G, N), bf16, 0.3))
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (L, C, H)), f32)
    half = jnp.full((L,), C // 2, jnp.int32)

    def chunk(h):
        y, h_out, snap = ssm.ssd_chunk(h, x, b, c, dt, a, half)
        return 0.5 * h_out + 0.25 * snap + 1e-3 * jnp.mean(y)
    ms = _median_ms(chunk, arr((L, H, P, N)), args.reps)
    # C B^T and its weighted sum over the causal half, the carried state's
    # readout, the state's update (and the snapshot's: a second one)
    flops = L * H * (2 * C * C * N / 2 + 2 * C * C * P / 2
                     + 2 * C * P * N + 2 * 2 * C * P * N)
    nbytes = L * (3 * H * P * N * 4 + C * (H * P + 2 * G * N) * 2
                  + C * H * P * 4)
    out["ssd_chunk"] = {"ms": ms, "flops": flops, "bytes": nbytes,
                        "roofline_pct": share(ms, flops, nbytes)}

    # the decode rows' in-place update, by live rows
    xd, bd, cd_ = (arr((S, H, P), bf16), arr((S, G, N), bf16, 0.3),
                   arr((S, G, N), bf16, 0.3))
    dtd = jnp.asarray(rng.uniform(0.001, 0.1, (S, H)), f32)
    out["ssm_decode_update"] = {}
    for live in sorted({1, 4, 8, S}):
        if live > S:
            continue
        mask = jnp.asarray((np.arange(S) < live).astype("float32"))

        def update(h, mask=mask):
            y, h = ssm.ssm_decode_update(h, mask, xd, bd, cd_, dtd,
                                         jnp.exp(dtd * a))
            return h.at[0, 0, 0, :y.shape[-1]].add(1e-6 * y[0, 0])
        ms = _median_ms(update, arr((S, H, P, N)), args.reps)
        flops, nbytes = live * 6 * H * P * N, live * 2 * H * P * N * 4
        out["ssm_decode_update"][str(live)] = {
            "ms": ms, "flops": flops, "bytes": nbytes,
            "roofline_pct": share(ms, flops, nbytes)}

    # the whole op of a mixed tick's layer, its state donated
    lanes = dict(lpos=jnp.asarray([[[256.0]]] * L), lrows=jnp.full((L,), C),
                 lslot=jnp.arange(L) + 1, src=jnp.full((L,), -1),
                 dst=jnp.arange(L), snap_rows=jnp.full((L,), C // 2))

    def scan(slot_h, slot_conv, snap_h, snap_conv, xbc, dt, taps, bias, live):
        return ssm.ssm_scan(
            xbc, dt, taps, bias, jnp.log(-a), jnp.zeros((H,), f32),
            jnp.ones((H,), f32), slot_h, slot_conv, live, (H, P, G, N),
            (snap_h, snap_conv, lanes["lpos"], lanes["lrows"], lanes["lslot"],
             lanes["src"], lanes["dst"], lanes["snap_rows"], C))

    live = jnp.asarray((np.arange(S) >= L + 1).astype("float32"))
    xbc, dtr = arr((S + L * C, cd), bf16), arr((S + L * C, H), bf16)
    taps, bias = arr((cd, K), bf16, 0.5), arr((cd,), bf16, 0.1)

    def whole(state):
        y, slot_h, *rest = scan(*state, xbc, dtr, taps, bias, live)
        # the rows' output is read, as a tick reads it
        return (slot_h.at[0, 0, 0, 0].add(1e-6 * jnp.mean(y)), *rest)
    ms = _median_ms(whole, (
        arr((S, H, P, N)), arr((S, K - 1, cd), bf16), arr((8, H, P, N)),
        arr((8, K - 1, cd), bf16)), args.reps)
    out["ssm_scan_mixed_layer"] = {"ms": ms,
                                   "live_decode_rows": int(live.sum())}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
