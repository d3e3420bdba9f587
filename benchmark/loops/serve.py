"""Serving cells: an open loop over engine.submit and engine.step, the loop
EngineServer._engine_loop runs, in one thread of this process.

Every request of the mix is due inside --seconds. It is submitted at the
first tick boundary at or after its due time (arrivals are polled between
ticks, as the server polls its queue) and run to its end; after the window
the loop drains for at most the mix's `drain_deadline_s`. A request that is
not finished by then, or ended in error, is `failed` and misses every
percentile. Times are taken from when a request was DUE, so a stall that
delays submission is charged to the requests it delays.

Around that loop (window and drain) the wall clock and the main thread's own
CPU time are taken: what is left of the wall after the thread's CPU time and
its `engine/wait` spans is time the machine's host took from the thread
(metrics/window_stolen_ms.py). The three numbers are in every run's `notes`,
beside the latencies they explain; no run is dropped or reweighted by them.
Set-up is counted from the process's start to the window's opening less
`runtime_start`, the first touch of the device (metrics/setup_s.py).

With --trace 1 a second, short open loop of the same mix follows the drain:
TRACE_LEAD_S seconds to fill the slots, then the cell's `trace_seconds` under
the profiler. Starting and stopping a trace stalls the host for seconds;
inside the window that would be charged to the requests in flight.
"""

from __future__ import annotations

import gc
import resource
import time

import numpy as np

from .. import harness, traffic

TRACE_LEAD_S = 4.0
# spans an engine step records (the benchmark's wrapper, admit, tick, dispatch
# and its three, wait, commit, finish) with room: a window recorded 82,000 at
# 6,088 ticks
SPANS_PER_TICK = 16
LANE_TOKENS = 128       # a prompt takes at most a tick a chunk of this many


def _thread_cpu_s():
    """User and system seconds of the calling thread."""
    u = resource.getrusage(resource.RUSAGE_THREAD)
    return u.ru_utime + u.ru_stime


def _size_span_ring(requests):
    """The program's span ring has to hold the window and the drain
    (`spans_since` raises where it wrapped). Upper bound of the ticks: every
    request alone in the engine, a tick a token and a tick a prompt chunk.
    Raised through the program's flag before the engine is built, never
    lowered: the chat cell at 3.2 req/s stayed inside the default."""
    from paddle_tpu.core import flags
    ticks = sum(r["max_new"] + len(r["prompt"]) // LANE_TOKENS + 2
                for r in requests)
    want = 1 << (SPANS_PER_TICK * ticks).bit_length()
    if want > int(flags.get_flag("trace_ring")):
        flags.set_flag("trace_ring", want)


class _Loop:
    """Submit requests when due and tick the engine, from `start()` on."""

    def __init__(self, engine, span, requests):
        self.engine, self.span = engine, span
        self.pending = [(r["due"], r["prompt"], r["max_new"]) for r in requests]
        self.handles, self.submitted = [], []
        self.peak_blocks = engine.pager.pool.n_used

    def start(self):
        self.t_open = time.perf_counter()
        return self.t_open

    def run_until(self, deadline_s, until_idle):
        """Tick until `deadline_s` after start(), or, with `until_idle`,
        until every request was sent and has finished."""
        engine, pending, n = self.engine, self.pending, len(self.pending)
        while True:
            now = time.perf_counter() - self.t_open
            i = len(self.handles)
            while i < n and pending[i][0] <= now:
                self.handles.append(engine.submit(pending[i][1], pending[i][2]))
                self.submitted.append(time.perf_counter())
                i += 1
            busy = engine.n_active or engine.n_pending
            if now >= deadline_s or (until_idle and i == n and not busy):
                return
            # with nothing to tick the loop spins on the clock until the next
            # request is due; it never sleeps (PERF.md, findings of PR 23, on
            # the tick's two speeds)
            if busy:
                with self.span("user", "benchmark/engine.step"):
                    engine.step()
                self.peak_blocks = max(self.peak_blocks,
                                       engine.pager.pool.n_used)


def _slot_ticks(engine):
    return (engine.busy_slot_ticks, engine.total_slot_ticks, engine.n_ticks)


def run(cell, args, t0):
    import jax
    from paddle_tpu.observability import tracing

    out = harness.start_run(cell, args, t0)
    parts, device = out.setup_parts, out.device
    t = time.perf_counter()
    compiles = harness.CompileCounter()
    mix, cfg, adapter = cell.traffic, cell.config, cell.adapter
    load = traffic.open_loop_requests(mix, args.seed, args.seconds,
                                      cfg["vocab"])
    _size_span_ring(load["requests"])
    parts["build"] = time.perf_counter() - t

    t = time.perf_counter()
    scope = adapter.build_weights(cfg, args.seed % (2 ** 31 - 1) + 1)
    engine = adapter.build_engine(cfg, cell.spec["engine"], scope)
    jax.block_until_ready(scope.get(adapter.param_names(cfg)[0]))
    parts["init"] = time.perf_counter() - t

    # warm: one request per system prompt (a bare token where the mix has
    # none). The first tick compiles or loads the one program the engine
    # runs; the prompts' blocks stay in the prefix cache.
    t = time.perf_counter()
    systems = load["system_prompts"] or [[1]]
    warm = [engine.submit(systems[0], 2)]
    engine.step()
    parts["compile_or_load"] = time.perf_counter() - t
    t = time.perf_counter()
    warm += [engine.submit(p, 2) for p in systems[1:]]
    engine.run_until_idle()
    if not all(h.done and h.error is None for h in warm):
        raise SystemExit("benchmark: the warm-up requests did not finish")
    parts["warm"] = time.perf_counter() - t

    loop = _Loop(engine, tracing.span, load["requests"])
    warm_ticks = [s.duration_ms for s in tracing.spans()
                  if s.name == "engine/tick"]
    gc.collect()
    gc.freeze()
    mark = tracing.mark()
    compiled_before = compiles.n
    at_open = _slot_ticks(engine)
    cpu_open = _thread_cpu_s()
    t_open = loop.start()
    out.open_window(t_open)
    loop.run_until(args.seconds, until_idle=False)
    at_close = _slot_ticks(engine)
    loop.run_until(args.seconds + mix["drain_deadline_s"], until_idle=True)
    t_end = time.perf_counter()
    out.window_clock = {"wall_s": t_end - t_open,
                        "thread_cpu_s": _thread_cpu_s() - cpu_open}
    at_end = _slot_ticks(engine)
    out.compiles_in_window = compiles.n - compiled_before
    out.spans = tracing.spans_since(mark)

    for k, spec in enumerate(load["requests"]):
        rec = {"due": t_open + spec["due"], "prompt_len": len(spec["prompt"]),
               "max_new": spec["max_new"], "ok": False}
        if k < len(loop.handles):
            h = loop.handles[k]
            rec.update(submitted=loop.submitted[k], admitted=h.admitted_pc,
                       first=h.first_token_pc, done=h.done_pc,
                       n_out=len(h.tokens), shared_len=h.shared_len,
                       ok=(h.done and h.error is None
                           and len(h.tokens) == spec["max_new"]))
        out.requests.append(rec)
    out.attempted = len(load["requests"])
    out.failed = sum(not r["ok"] for r in out.requests)
    out.counters = {
        "busy_slot_ticks_window": at_close[0] - at_open[0],
        "total_slot_ticks_window": at_close[1] - at_open[1],
        "busy_slot_ticks": at_end[0] - at_open[0],
        "ticks": at_end[2] - at_open[2],
        "peak_blocks_used": loop.peak_blocks,
        "n_blocks": engine.n_blocks, "n_slots": engine.n_slots}
    quarters = [t_open + q * args.seconds for q in (0.25, 0.5, 0.75, 1.0)]
    out.notes = {"requests": out.attempted,
                 "drain_s": max(t_end - t_open - args.seconds, 0.0),
                 # the machine beside the program (metrics/window_stolen_ms.py):
                 # wall, the main thread's own CPU time and its waits for the
                 # device over window and drain; what is left was taken away
                 "window_wall_s": out.window_clock["wall_s"],
                 "window_thread_cpu_s": out.window_clock["thread_cpu_s"],
                 "window_engine_wait_s": sum(out.span_ms("engine/wait")) / 1e3,
                 "window_stolen_ms": harness.load_module(
                     "metrics", "window_stolen_ms").read(out),
                 # requests due and not yet done at each quarter of the window:
                 # a backlog that grows from quarter to quarter is past the knee
                 "in_system_at_quarters": [
                     sum(1 for r in out.requests if r["due"] <= t
                         and (r.get("done") is None or r["done"] > t))
                     for t in quarters],
                 "ticks": out.counters["ticks"], # the tick has two speeds, a process keeps one (PERF.md): say which
                 "tick_ms_p50_warm": harness.quantile(warm_ticks, 0.5),
                 "tick_ms_p50": harness.quantile(out.span_ms("engine/tick"), 0.5),
                 "dispatch_ms_p50": harness.quantile(
                     out.span_ms("engine/dispatch"), 0.5)}
    out.correct = out.failed == 0 and _check(cell, scope, loop.handles, load,
                                             out)
    if args.trace:
        out.trace = _traced_phase(cell, args, engine, tracing.span)
    gc.unfreeze()
    return out


def _traced_phase(cell, args, engine, span):
    """More of the same mix under the profiler: the same seed, so the same
    system prompts, which the prefix cache still holds."""
    length = TRACE_LEAD_S + cell.spec["trace_seconds"]
    again = traffic.open_loop_requests(cell.traffic, args.seed, length,
                                       cell.config["vocab"])
    loop = _Loop(engine, span, again["requests"])
    loop.start()
    loop.run_until(TRACE_LEAD_S, until_idle=False)
    with harness.Profiler() as profiler:
        loop.run_until(length, until_idle=False)
    return profiler.result()


def _check(cell, scope, handles, load, out):
    """Prefill and decode through the paged cache agree with a full forward
    pass: for a seeded sample of finished requests, every emitted token's
    reference logit is within `logit_gap_tol` (in units of that position's
    standard deviation of logits) of the position's largest. Logits and not
    tokens are compared, because under random weights the largest logit
    changes on rounding."""
    cfg, adapter = cell.config, cell.adapter
    params = {n: scope.get(n) for n in adapter.param_names(cfg)}
    done = [k for k, h in enumerate(handles) if h.done and h.error is None]
    pick = traffic.rng_for(out.seed, "check").permutation(len(done))
    worst = 0.0
    for k in (done[j] for j in pick[:cell.spec["check_requests"]]):
        prompt, toks = load["requests"][k]["prompt"], handles[k].tokens
        seq = np.asarray(prompt + toks[:-1], np.int32)
        ref = adapter.reference_logits(cfg, params, seq,
                                       cell.spec["engine"]["max_len"])
        ref = ref[len(prompt) - 1:]
        gap = (ref.max(-1) - ref[np.arange(len(toks)), toks]) / ref.std(-1)
        worst = max(worst, float(gap.max()))
    out.notes.update(check_requests=min(len(done), cell.spec["check_requests"]),
                     check_worst_logit_gap=worst,
                     check_tol=cell.spec["logit_gap_tol"])
    out.checks["worst_logit_gap"] = (worst, cell.spec["logit_gap_tol"])
    return bool(done) and worst <= cell.spec["logit_gap_tol"]
