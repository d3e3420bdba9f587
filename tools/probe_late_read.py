"""The serving tick's ids read a launch late, timed ALONE on the chip (PR 44;
the orders with the copy started at the launch: PR 55):

    chiprun --chips 1 -- python3 tools/probe_late_read.py [--cell <cell>]
                                                          [--ticks 300]

Builds a serving cell's engine (default `lm-big_serve_chat`: its weights, its
16 slots, its pools), brings `--live` requests into decode at chat lengths,
and then runs `--ticks` decode ticks of the SAME slots (positions frozen: the
work of every tick is the same) through the bound step in these orders, the
scheduler left out:

- `eager`:       fill, launch k, `np.asarray(ids k)`             (the parent's)
- `late`:        fill, launch k+1, `np.asarray(ids k)`, block on k+1
- `late_async`:  as `late`, and `ids k.copy_to_host_async()` right after the
                 block on k, before the fill
- `late_noread`: fill, launch k+1, block on k+1 (what no read at all costs)
- `late_at_launch`: fill, launch k+1, `ids k+1.copy_to_host_async()` right
                 behind that launch, `np.asarray(ids k)`, block on k+1: the
                 copy of a tick is ENQUEUED WITH the tick (what the engine
                 does since PR 55), and the read a launch later finds the
                 bytes on the host
- `late_thread`: as `late`, and a reader thread that is handed `ids k+1` at
                 the launch and leaves `np.asarray` of them where the read
                 finds them (the same mechanism by other means, should the
                 runtime's own enqueue not do)
- `ahead`, `ahead_at_launch`: the engine's order since PR 48 (fill, launch
                 k+1, block on k, `np.asarray(ids k)`: nothing waits for k+1),
                 without and with the copy enqueued at the launch: here the
                 read comes the instant the block returns, and says whether
                 the copy's arrival is later than the block's (on a TPU v5e
                 it is, by 0.35 ms: the runtime starts the copy only once its
                 own host side has seen the tick done; PERF.md section 6, PR
                 55)

What `late_async` (PR 41, PR 44) could not show: there the copy was enqueued
AFTER the wait for the tick, on an array that was already complete, in an
iteration the DEVICE bounds (every order but `ahead*` blocks on k+1 before it
goes on). Host time saved is invisible in such an iteration and host time
added delays the launch, so the order could only read even or worse (0.06-0.2
ms worse). What decides the mechanism is not the iteration but the READ's own
time (`asarray_ms_p50`: the part that stands on the launching thread in an
engine whose period is the host's) and what the enqueue adds to the launch
(`launch_ms_p50`, fill + launch + enqueue, against `late`'s).

In the late orders the decode rows take their token from the device
(`tick_from_last` 1). One JSON line: per order the medians in ms of a whole
iteration, of fill + launch (+ enqueue), of the `np.asarray` and of the block;
`asarray_blocks`: whether the read of the complete tick k waited for the
queued tick k+1 (its median above half the tick's), for `late` and for
`late_at_launch`; `launch_added_ms`: `late_at_launch`'s fill + launch less
`late`'s; and `way_back`: what the host sees of a finished array, for the
pacer's "seen done" (`sync_read_ms`: `np.asarray` of a fresh array that is
complete, no copy ahead of it; `tiny_block_ms`: a one-op program's launch
return to its block's return and launch start to block's return, which bracket
the runtime's own way from the device's last op to the host)."""

from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _med(v):
    return round(1e3 * float(np.median(v)), 4)


ORDERS = ("eager", "late", "late_async", "late_noread", "late_at_launch",
          "late_thread", "ahead", "ahead_at_launch")


class _Reader:
    """A thread that is handed a fetch at its launch and leaves its ids where
    `take` finds them (`np.asarray` gives the lock away while it waits)."""

    def __init__(self):
        self._q, self._got = queue.SimpleQueue(), {}
        self._have = threading.Condition()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            fetch = self._q.get()
            if fetch is None:
                return
            ids = np.asarray(fetch)
            with self._have:
                self._got[id(fetch)] = ids
                self._have.notify()

    def hand(self, fetch):
        self._q.put(fetch)

    def take(self, fetch) -> np.ndarray:
        with self._have:
            while id(fetch) not in self._got:
                self._have.wait()
            return self._got.pop(id(fetch))

    def close(self):
        self._q.put(None)
        self._thread.join(timeout=5)


def way_back(n=40):
    """What the host sees of an array that is complete -> medians in ms."""
    import jax
    host = np.zeros((16, 1), np.int32)
    bump = jax.jit(lambda x: x + 1)
    x = jax.device_put(host)
    bump(x).block_until_ready()
    sync, after, whole = [], [], []
    for _ in range(n):
        y = bump(x)
        y.block_until_ready()
        time.sleep(0.002)               # long complete
        t = time.perf_counter()
        np.asarray(y)
        sync.append(time.perf_counter() - t)
        t0 = time.perf_counter()
        y = bump(x)
        t1 = time.perf_counter()
        y.block_until_ready()
        t2 = time.perf_counter()
        after.append(t2 - t1)
        whole.append(t2 - t0)
    return {"sync_read_ms": _med(sync), "sync_read_ms_min": round(
        1e3 * min(sync), 4), "tiny_block_ms": [_med(after), _med(whole)]}


def probe(eng, vocab, live, prompt, ticks):
    """The orders over `eng`, `live` requests of `prompt` tokens in decode
    -> {order: medians}."""
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
        whole, launch, read, block = [], [], [], []
        reader = _Reader() if order == "late_thread" else None
        at_launch = order.endswith("_at_launch")
        fill(order != "eager")
        prev = eng._launch_tick()[0]
        if at_launch:
            prev.copy_to_host_async()
        if reader:
            reader.hand(prev)
        prev.block_until_ready()
        for _ in range(ticks):
            t0 = time.perf_counter()
            if order == "late_async":
                prev.copy_to_host_async()
            fill(order != "eager")
            ids = eng._launch_tick()[0]
            if at_launch:
                ids.copy_to_host_async()
            if reader:
                reader.hand(ids)
            t1 = time.perf_counter()
            if order == "eager":
                np.asarray(ids)
                t3 = time.perf_counter()
                read.append(t3 - t1)
                block.append(0.0)
            elif order.startswith("ahead"):
                prev.block_until_ready()
                t2 = time.perf_counter()
                np.asarray(prev)
                t3 = time.perf_counter()
                block.append(t2 - t1)
                read.append(t3 - t2)
            else:
                if reader:
                    reader.take(prev)
                elif order != "late_noread":
                    np.asarray(prev)
                t2 = time.perf_counter()
                ids.block_until_ready()
                t3 = time.perf_counter()
                read.append(t2 - t1)
                block.append(t3 - t2)
            prev = ids
            whole.append(t3 - t0)
            launch.append(t1 - t0)
        prev.block_until_ready()
        if reader:
            reader.take(prev)
            reader.close()
        return {"iteration_ms_p50": _med(whole), "launch_ms_p50": _med(launch),
                "asarray_ms_p50": _med(read), "asarray_ms_p90": round(
                    1e3 * float(np.quantile(read, 0.9)), 4),
                "block_ms_p50": _med(block),
                "iteration_ms_p10": round(1e3 * float(np.quantile(whole, 0.1)),
                                          4)}

    orders = {}
    for rep in range(2):                # twice: the first pass warms the host
        for order in ORDERS + ("eager",):
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
        "way_back": way_back(),
        # did the read of the complete tick k wait for the queued tick k+1?
        "asarray_blocks": {
            order: bool(by[order]["asarray_ms_p50"]
                        > 0.5 * by["late_noread"]["block_ms_p50"])
            for order in ("late", "late_at_launch")},
        # what the enqueue behind the launch adds to the launching thread
        "launch_added_ms": round(by["late_at_launch"]["launch_ms_p50"]
                                 - by["late"]["launch_ms_p50"], 4)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
