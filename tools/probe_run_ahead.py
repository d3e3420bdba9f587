"""What run-ahead of the serving tick rests on, timed ALONE on the chip (PR 48):

    chiprun --chips 1 -- python3 tools/probe_run_ahead.py [--cell <cell>]
                                                          [--ticks 300]

Builds a serving cell's engine (default `lm-big_serve_chat`), brings `--live`
requests into decode, and reads, the scheduler left out:

- `pack`: is the ONE host buffer a launch transfers free again when the launch
  returns? `--ticks` launches behind a busy device, the buffer overwritten the
  instant each returns; the ids must be those of the values launched (`stale`
  counts launches that saw the overwrite). Only for a model without a
  recurrent state: a state-space or conv layer's state moves on with every
  launch, the ids with it, and every launch reads as stale.
- `host`: what waiting costs on this host: `time.sleep(x)`'s overrun for x
  from 0 to 5 ms, `is_ready()`'s own time, and the wake-up of
  `block_until_ready()` (a tick's time seen by the block less the same seen by
  polling `is_ready`).
- `orders`: decode ticks of the SAME slots through the bound step,
  `late` (fill, launch k+1, `np.asarray` k, block on k+1: the parent's order),
  `ahead` (fill, launch k+1, block on k, `np.asarray` k: nothing waits for
  k+1, so the next launch is queued behind it) and `ahead_poll` (the same with
  the block replaced by polling `is_ready`); per order the median period and
  the parts. `noread` (launch k+1, block on k) is the device's own period
  with the host's launch hidden.

One JSON line."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _med(v):
    return round(1e3 * float(np.median(v)), 4)


def host_costs(launch):
    """-> sleep overruns, `is_ready` cost, the block's wake-up (ms)."""
    out = {"sleep_overrun_ms_p50": {}, "sleep_overrun_ms_p90": {}}
    for x in (0.0, 5e-5, 2e-4, 1e-3, 5e-3):
        over = []
        for _ in range(60):
            t = time.perf_counter()
            time.sleep(x)
            over.append(time.perf_counter() - t - x)
        out["sleep_overrun_ms_p50"][str(x)] = _med(over)
        out["sleep_overrun_ms_p90"][str(x)] = round(
            1e3 * float(np.quantile(over, 0.9)), 4)
    ids = launch()
    ids.block_until_ready()
    t = time.perf_counter()
    for _ in range(1000):
        ids.is_ready()
    out["is_ready_us"] = round(1e3 * (time.perf_counter() - t), 3)
    seen = {"block": [], "poll": [], "poll_yield": []}
    for how in ("block", "poll", "poll_yield") * 40:
        t = time.perf_counter()
        ids = launch()
        if how == "block":
            ids.block_until_ready()
        else:
            while not ids.is_ready():
                if how == "poll_yield":
                    time.sleep(0)
        seen[how].append(time.perf_counter() - t)
    out["tick_seen_ms_p50"] = {k: _med(v) for k, v in seen.items()}
    out["block_wakeup_ms"] = round(out["tick_seen_ms_p50"]["block"]
                                   - out["tick_seen_ms_p50"]["poll"], 4)
    return out


def pack_reuse(eng, fill, launch, ticks):
    """Launch behind a busy device and overwrite the pack at once -> how
    many launches saw the overwrite."""
    fill(False)
    want = np.asarray(launch())          # the ids of the values filled
    stale = 0
    for _ in range(ticks):
        fill(False)
        busy = [launch() for _ in range(3)]      # the device has work queued
        ids = launch()
        eng._tok[:] = 7                  # another token in every row
        eng._pos[:] = 3.0
        got = np.asarray(ids)
        del busy
        stale += int(not np.array_equal(got, want))
    return {"launches": ticks, "stale": stale}


def orders(eng, fill, launch, ticks):
    def poll(ids):
        while not ids.is_ready():
            pass

    def timed(order):
        whole, wait, read = [], [], []
        fill(True)
        prev = launch()
        for _ in range(ticks):
            t0 = time.perf_counter()
            fill(True)
            ids = launch()
            t1 = time.perf_counter()
            if order == "late":
                np.asarray(prev)
                t2 = time.perf_counter()
                ids.block_until_ready()
                t3 = time.perf_counter()
                wait.append(t3 - t2)
                read.append(t2 - t1)
            else:
                poll(prev) if order == "ahead_poll" else \
                    prev.block_until_ready()
                t2 = time.perf_counter()
                if order != "noread":
                    np.asarray(prev)
                t3 = time.perf_counter()
                wait.append(t2 - t1)
                read.append(t3 - t2)
            prev = ids
            whole.append(t3 - t0)
        prev.block_until_ready()
        return {"period_ms_p50": _med(whole), "launch_ms_p50": _med(
            [w - a - b for w, a, b in zip(whole, wait, read)]),
                "wait_ms_p50": _med(wait), "asarray_ms_p50": _med(read),
                "period_ms_p10": round(1e3 * float(np.quantile(whole, 0.1)),
                                       4)}

    out = {}
    for rep in range(2):                # twice: the first pass warms the host
        for order in ("late", "ahead", "ahead_poll", "noread", "late"):
            out[f"{order}.{rep}.{len(out)}"] = timed(order)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cell", default="lm-big_serve_chat")
    ap.add_argument("--ticks", type=int, default=300)
    ap.add_argument("--live", type=int, default=6)
    ap.add_argument("--prompt", type=int, default=190)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    from benchmark import harness
    cell = harness.Cell(args.cell)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("probe_run_ahead: a time comes from the chip alone")
    cfg = cell.config
    scope = cell.adapter.build_weights(cfg, 7)
    eng = cell.adapter.build_engine(cfg, cell.spec["engine"], scope)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, cfg["vocab"], args.prompt).tolist(), 64)
            for _ in range(args.live)]
    while any(r.first_token_pc is None for r in reqs):
        eng.step()
    for _ in range(8):                  # decode ticks: the second program
        eng.step()
    eng._late_ok = False
    eng.step()                          # nothing unread is left behind
    with eng._lock:
        active = dict(eng._active)
    assert len(active) == args.live and eng._uncommitted is None

    def fill(from_device):
        for r in active.values():
            r.next_tok = None if from_device else 1
        eng._fill_tick_feeds(active)
        eng._lanes = ()

    def launch():
        return eng._launch_tick()[0]

    print(json.dumps({
        "cell": args.cell, "ticks": args.ticks, "live": args.live,
        "device": jax.devices()[0].device_kind,
        "pack": pack_reuse(eng, fill, launch, args.ticks),
        "host": host_costs(launch),
        "orders": orders(eng, fill, launch, args.ticks)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
