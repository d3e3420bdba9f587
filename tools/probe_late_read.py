"""The serving tick's ids read a launch late, timed ALONE on the chip (PR 44):

    chiprun --chips 1 -- python3 tools/probe_late_read.py [--cell <cell>]
                                                          [--ticks 300]

Builds a serving cell's engine (default `lm-big_serve_chat`: its weights, its
16 slots, its pools), brings `--live` requests into decode at chat lengths,
and then runs `--ticks` decode ticks of the SAME slots (positions frozen: the
work of every tick is the same) through the bound step in four orders, the
scheduler left out:

- `eager`:       fill, launch k, `np.asarray(ids k)`             (the parent's)
- `late`:        fill, launch k+1, `np.asarray(ids k)`, block on k+1
- `late_async`:  as `late`, and `ids k.copy_to_host_async()` right after the
                 block on k, before the fill
- `late_noread`: fill, launch k+1, block on k+1 (what no read at all costs)

In the late orders the decode rows take their token from the device
(`tick_from_last` 1). One JSON line: per order the medians in ms of a whole
iteration, of the `np.asarray` and of the block; and `asarray_blocks`: whether
the read of the complete tick k waited for the queued tick k+1 (its median
above half the tick's)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def probe(eng, vocab, live, prompt, ticks):
    """The four orders over `eng`, `live` requests of `prompt` tokens in
    decode -> {order: medians}."""
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, vocab, prompt).tolist(), 64)
            for _ in range(live)]
    while any(r.first_token_pc is None for r in reqs):
        eng.step()
    for _ in range(8):                  # decode ticks: the second program
        eng.step()
    eng._late_ok = False
    eng.step()                          # nothing unread is left behind
    with eng._lock:
        active = dict(eng._active)
    assert len(active) == live and eng._uncommitted is None

    def fill(from_device):
        for r in active.values():
            r.next_tok = None if from_device else 1
        eng._fill_tick_feeds(active)
        eng._lanes = ()

    def timed(order):
        whole, read, block = [], [], []
        fill(order != "eager")
        prev = eng._launch_tick()[0]
        prev.block_until_ready()
        for _ in range(ticks):
            t0 = time.perf_counter()
            if order == "late_async":
                prev.copy_to_host_async()
            fill(order != "eager")
            ids = eng._launch_tick()[0]
            t1 = time.perf_counter()
            if order == "eager":
                np.asarray(ids)
                t2 = t3 = time.perf_counter()
            else:
                if order != "late_noread":
                    np.asarray(prev)
                t2 = time.perf_counter()
                ids.block_until_ready()
                t3 = time.perf_counter()
            prev = ids
            whole.append(t3 - t0)
            read.append(t2 - t1)
            block.append(t3 - t2)
        med = lambda v: round(1e3 * float(np.median(v)), 4)  # noqa: E731
        return {"iteration_ms_p50": med(whole), "asarray_ms_p50": med(read),
                "block_ms_p50": med(block),
                "iteration_ms_p10": round(1e3 * float(np.quantile(whole, 0.1)),
                                          4)}

    orders = {}
    for rep in range(2):                # twice: the first pass warms the host
        for order in ("eager", "late", "late_async", "late_noread", "eager"):
            orders[f"{order}.{rep}.{len(orders)}"] = timed(order)
    return orders


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
        raise SystemExit("probe_late_read: a time comes from the chip alone")
    cfg = cell.config
    scope = cell.adapter.build_weights(cfg, 7)
    eng = cell.adapter.build_engine(cfg, cell.spec["engine"], scope)
    orders = probe(eng, cfg["vocab"], args.live, args.prompt, args.ticks)
    by = {k.split(".")[0]: v for k, v in orders.items()}    # the last of each
    print(json.dumps({
        "cell": args.cell, "ticks": args.ticks, "live": args.live,
        "device": jax.devices()[0].device_kind, "orders": orders,
        # did the read of the complete tick k wait for the queued tick k+1?
        "asarray_blocks": bool(by["late"]["asarray_ms_p50"]
                               > 0.5 * by["late_noread"]["block_ms_p50"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
