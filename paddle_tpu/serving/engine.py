"""Continuous-batching serving engine over the fused decode path.

≙ reference inference/api/api_impl.cc:126 — the serving hot loop as a
first-class perf surface — extended with the scheduling idea the reference
era didn't have: requests of different lengths share ONE compiled decode
program through a slot-indexed KV cache, so a new request joins the
in-flight batch the tick a slot frees instead of waiting for a static
batch to drain.

The pieces:

- `transformer_lm_decode_tick` (models/transformer.py) — one decode tick
  over persistable [S,1,nh,T,dh] slot caches with PER-SLOT positions
  (`cache_write(batch_axis=0)`, closing the uniform-`Pos` limitation for
  real), compiled once; fuse_decode_attention_pass rewrites its attention
  chains into the r06 fused decode kernel.
- `SlotAllocator` — free-list over the S cache rows; alloc on admission,
  free on completion. A reused slot needs NO cache reset: the per-slot
  mask exposes only positions <= the slot's own pos, and prefill rewrites
  rows 0..P-1 before they are ever exposed (asserted in
  tests/test_serving_engine.py).
- `ContinuousBatchingEngine` — request queue + scheduler + tick loop.
  Prefill is teacher-forced through the same tick program, one prompt
  token a tick (the fed token is the next prompt token until the prompt
  is consumed, then the slot's previously sampled token), so one
  executable serves every mixture of request phases: the identity oracle
  of the paged engine, whose prompts go many tokens a launch through the
  prefill lanes of a second, mixed tick program (`PagedKVEngine`,
  serving/kv_pager.py; `stats()["prefill"]` says which). Dispatch rides
  `Executor.prepare` — the per-call validation/signature-hash overhead
  is off the tick path.
- `EngineServer`/`EngineClient` — generation RPC over the serving.py v2
  transport (vectored frames, batched writes): the engine thread ticks
  while reader/writer threads move bytes, so decode and socket I/O
  overlap; completions landing on the same tick go out as one vectored
  send.

Scheduling policies:

- "continuous": admit whenever a slot is free — the engine's point.
- "static": admit only when ALL slots are free (form a batch, run it to
  full completion, drain, repeat) — the padded static-batch baseline.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.enforce import InvalidArgumentError, enforce
from ..observability import memory as _obs_memory
from ..observability import metrics as _obs_metrics
from ..observability import tracing as _tracing

# atomic in CPython: concurrent engine construction must not mint the
# same cache namespace (aliased slot caches in a shared scope)
_ENGINE_SEQ = __import__("itertools").count(1)


class SlotAllocator:
    """Free-list allocator over the decode batch's S cache rows."""

    def __init__(self, n_slots: int):
        enforce(n_slots >= 1, "need at least one slot",
                exc=InvalidArgumentError)
        self.n_slots = n_slots
        self._free = list(range(n_slots - 1, -1, -1))
        self._used = set()

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        s = self._free.pop()
        self._used.add(s)
        return s

    def free(self, slot: int):
        enforce(slot in self._used, f"slot {slot} not allocated",
                exc=InvalidArgumentError)
        self._used.remove(slot)
        self._free.append(slot)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._used)


#: Edges of `ptpu_engine_tick_latency_seconds`. A decode tick of a model
#: worth serving takes 10-100 ms, and the p50/p95/p99 gauges interpolate
#: inside the winning bucket: so that decade has an edge every ~10%
#: (10 ms x 10^(k/24)), the decades around it the usual 1-2.5-5.
TICK_LATENCY_BUCKETS = (
    1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    *(round(1e-2 * 10 ** (k / 24), 5) for k in range(25)),
    0.25, 0.5, 1.0, 2.5)


class GenRequest:
    """One generation request moving through the engine.

    Besides the wall-clock fields (`submitted_at`/`first_token_at`/
    `done_at`, kept for API compatibility), every lifecycle boundary is
    also stamped on the perf_counter clock — the monotonic timeline the
    trace ring uses — so the request's latency DECOMPOSES conservatively:

        queue_wait = admitted - submitted       (waiting for a slot)
        prefill    = first_token - admitted     (prompt ticks, TTFT part)
        decode     = done - first_token         (sampled-token ticks)
        transport  = sent - done                (completion frame on the
                                                 wire; 0 without a server)

    The four phases partition [submitted, sent] exactly — their sum IS
    the end-to-end latency (a tolerance on that sum is float noise
    headroom, not slack in the definition). `request_id` threads
    from EngineClient through admission, every tick's span attrs, and
    the completion frame."""

    __slots__ = ("rid", "request_id", "prompt", "max_new", "eos_id",
                 "tokens", "slot", "fed", "next_tok", "submitted_at",
                 "first_token_at", "done_at", "on_done", "_event",
                 "submitted_pc", "admitted_at", "admitted_pc",
                 "first_token_pc", "done_pc", "sent_at", "sent_pc",
                 "defer_transport", "table", "shared_len",
                 "spec_draft_s", "spec_verify_s", "error",
                 "admitted_tick", "ticks_to_first", "lane_wait_ticks",
                 "closed")

    def __init__(self, rid, prompt, max_new, eos_id=None, on_done=None,
                 request_id: Optional[str] = None,
                 defer_transport: bool = False):
        self.rid = rid
        self.request_id = str(request_id) if request_id is not None \
            else f"req-{rid}"
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.tokens: List[int] = []
        self.slot: Optional[int] = None
        self.fed = 0                       # positions consumed so far
        #: token the next tick feeds; None: the one the last tick sampled,
        #: still on the device (that tick's ids are read a launch late)
        self.next_tok: Optional[int] = self.prompt[0]
        #: takes no further row: it ended by count on a tick whose ids are
        #: not read yet, or its `eos` was read while its next row was in
        #: flight. It keeps its slot (and stays in the engine's `_active`)
        #: until its completion is delivered and that row is dropped
        self.closed = False
        self.submitted_at = time.time()
        self.submitted_pc = time.perf_counter()
        self.admitted_at: Optional[float] = None
        self.admitted_pc: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.first_token_pc: Optional[float] = None
        self.done_at: Optional[float] = None
        self.done_pc: Optional[float] = None
        self.sent_at: Optional[float] = None
        self.sent_pc: Optional[float] = None
        self.on_done = on_done
        #: the way to the first token, counted where it is scheduled: the
        #: engine's tick count at admission; then the engine ticks from
        #: the one that admitted the request to the one that emitted its
        #: first token, inclusive (1: the tick it was admitted before);
        #: and the ticks of those in which it was in prefill and got no
        #: lane (chunked paged engine; 0 anywhere else)
        self.admitted_tick = 0
        self.ticks_to_first = 0
        self.lane_wait_ticks = 0
        #: paged-KV engine state: the request's BlockTable, and how many
        #: leading prompt positions were satisfied from the prefix cache
        #: (prefill starts at `shared_len` instead of 0). None/0 on the
        #: slot engine.
        self.table = None
        self.shared_len = 0
        #: speculative-decoding sub-phase accumulators: wall seconds this
        #: request spent inside `speculate` (draft ticks) and `verify`
        #: (target forward) rounds — SUB-phases of prefill+decode, not a
        #: fifth/sixth partition member (phases(subphases=True))
        self.spec_draft_s = 0.0
        self.spec_verify_s = 0.0
        #: True when a server OWNS the transport phase (it will call
        #: engine.report_sent once the completion frame is on the wire
        #: — or immediately if the frame cannot be delivered); False =
        #: no wire, transport/e2e close at completion
        self.defer_transport = bool(defer_transport)
        #: the exception that killed the engine under this request
        #: (`ContinuousBatchingEngine.fail_all`); None on a normal finish
        self.error: Optional[BaseException] = None
        self._event = threading.Event()

    @property
    def done(self) -> bool:
        return self.done_at is not None

    @property
    def latency_s(self) -> Optional[float]:
        return (self.done_at - self.submitted_at) if self.done else None

    def phases(self, subphases: bool = False) -> Optional[Dict[str, float]]:
        """{queue_wait, prefill, decode, transport} seconds (transport 0
        until/unless a server reports the completion frame sent); None
        before completion. The four phases always partition
        [submitted, sent] exactly. With `subphases=True`, a request
        served speculatively additionally reports `spec_draft` and
        `spec_verify` — SUB-phases of the prefill+decode window (their
        sum is bounded by prefill+decode, not added to the partition)."""
        if self.done_pc is None:
            return None
        first = self.first_token_pc if self.first_token_pc is not None \
            else self.done_pc
        ph = {
            "queue_wait": self.admitted_pc - self.submitted_pc,
            "prefill": first - self.admitted_pc,
            "decode": self.done_pc - first,
            "transport": ((self.sent_pc - self.done_pc)
                          if self.sent_pc is not None else 0.0),
        }
        if subphases:
            ph["spec_draft"] = self.spec_draft_s
            ph["spec_verify"] = self.spec_verify_s
        return ph

    def e2e_s(self) -> Optional[float]:
        """Measured end-to-end latency on the perf_counter clock:
        submit → completion frame sent (→ completion when no server is
        involved). The number the phase decomposition must sum to."""
        if self.done_pc is None:
            return None
        end = self.sent_pc if self.sent_pc is not None else self.done_pc
        return end - self.submitted_pc

    def wait(self, timeout: Optional[float] = None) -> List[int]:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} not done in {timeout}s")
        if self.error is not None:
            raise RuntimeError(
                f"engine failed under request {self.rid}") from self.error
        return self.tokens

    def _complete(self):
        self.done_at = time.time()
        self.done_pc = time.perf_counter()
        if self.on_done is not None:
            self.on_done(self)
        self._event.set()


class _Running:
    """Running mean of a reading, and of its distance from that mean. A
    reading over twice the mean counts as twice the mean (a compile inside
    a launch, a stall of the host: not what the next one will take); one
    under a quarter of it replaces it (the first reading was such a one)."""

    __slots__ = ("mean", "dev")

    def __init__(self, mean: Optional[float] = None):
        self.mean = mean
        self.dev = 0.0

    def add(self, x: float):
        mean = self.mean
        if mean is None or x < mean / 4:
            self.mean = x
        else:
            x = min(x, 2 * mean)
            self.dev += (abs(x - mean) - self.dev) / 8
            self.mean = mean + (x - mean) / 8


def seen_done_lag_s(reads: int = 5) -> float:
    """How much later than the device the host sees a tick done, taken on
    the default device: the median of `reads` synchronous reads of a small
    array that is complete (`np.asarray`, no copy ahead of it). 0.4-0.5 ms
    on a TPU's host, microseconds on the CPU backend. It is what the pacer
    took off before the ids' copy started with the launch (the running mean
    of the tick's own read, 0.41-0.46 ms): the runtime's own way from the
    device's last op to the host (~0.25 ms on a TPU: PERF.md section 6, PR
    48) and a copy's enqueue and completion on top, so a launch timed by it
    comes 0.15-0.2 ms early, on purpose: late costs the chip as much, early
    costs an arrival a tick only if it falls into that stretch."""
    import jax
    ids = np.zeros((8, 1), np.int32)
    lags = []
    for _ in range(reads):
        on_device = jax.device_put(ids)
        on_device.block_until_ready()
        t = time.perf_counter()
        np.asarray(on_device)
        lags.append(time.perf_counter() - t)
    return float(np.median(lags))


class _Launched:
    """A tick on the device whose ids the host has not read: its
    `engine/tick` span, the fetch, the (request, row) pairs whose row is a
    sampled token, the program that runs it, when its launch returned and
    when the device was done with it (None: not seen yet)."""

    __slots__ = ("tick", "fetch", "emits", "program", "launched_at",
                 "done_at")

    def __init__(self, tick, fetch, program, launched_at):
        self.tick, self.fetch, self.program = tick, fetch, program
        self.launched_at = launched_at
        self.emits: List[tuple] = []
        self.done_at: Optional[float] = None


class _TickPacer:
    """When to give the thread back while a tick is still on the device:
    one launch's own time before the device is done with it, so that the
    next tick is queued just as the device comes free and whoever polls
    arrivals between two steps still gets them into the very next tick.
    Everything is estimated from what the engine sees, on `clock`:

    - `way_back_s`: how much later than the device the host sees a tick
      done (`seen_done_lag_s`, taken once when the engine is built: what a
      synchronous read of a small array that is complete takes). `done`
      takes it off. It does not come from the tick's own read: the ids'
      copy is enqueued with the launch (`_plain_tick`), so that read takes
      what is left of the copy's way, down to nothing;
    - `device_s[program]`: the device times last seen for a tick program,
      each from the later of (the tick before done, this tick's launch
      returned) to this tick done; the hold goes by the LEAST of the last
      three (one seen across a stall of the host is too long, and a hold
      that is too long leaves the chip idle and is then seen as long again;
      one too short only queues the next tick early);
    - the lead a launch needs (`lead_s`): the running mean of the host's
      own stretch from `step()`'s entry to the launch's return, plus that
      of the caller's gap between two steps (return to next entry), plus
      four times their running deviations (a launch that comes late leaves
      the chip idle for as long; one that comes early costs an arrival a
      tick only if it falls into that stretch);
    - what a sleep costs on this host: the shortest sleep it gives
      (`nap_floor_s`, taken once, here) and the running mean of what the
      hold's own sleeps overran (`oversleep`)."""

    #: a read of a tick's ids that returns within this found them on the
    #: host (`found` on `engine/copy_back`): such a read is 0.02-0.04 ms on
    #: a TPU's host, one that waits for the copy 0.15 ms and more
    FOUND_WITHIN_S = 1e-4

    __slots__ = ("clock", "sleep", "device_s", "own", "gap", "way_back_s",
                 "nap_floor_s", "oversleep", "entered_at", "left_at",
                 "free_at")

    def __init__(self, clock=time.perf_counter, sleep=time.sleep,
                 way_back_s: float = 0.0):
        self.clock, self.sleep, self.way_back_s = clock, sleep, way_back_s
        self.device_s: Dict[str, deque] = {}
        self.free_at = float("-inf")    # the device was never seen busy
        self.own, self.gap = _Running(), _Running()
        naps = []
        for _ in range(3):
            t = clock()
            sleep(1e-9)
            naps.append(clock() - t)
        self.nap_floor_s = min(naps)
        self.oversleep = _Running(self.nap_floor_s)
        self.entered_at = self.left_at = None

    def entered(self):
        """`step()` was entered (after `left`: the caller's gap ends)."""
        self.entered_at = now = self.clock()
        if self.left_at is not None:
            self.gap.add(now - self.left_at)
            self.left_at = None

    def launched(self, tick, fetch, program: str) -> _Launched:
        """The launch of `tick` returned just now."""
        now = self.clock()
        self.own.add(now - self.entered_at)
        return _Launched(tick, fetch, program, now)

    def left(self):
        """`step()` returns with a tick on the device."""
        self.left_at = self.clock()

    def lead_s(self) -> float:
        own, gap = self.own, self.gap
        return own.mean + (gap.mean or 0.0) + 4 * (own.dev + gap.dev)

    def _started(self, run: _Launched) -> float:
        """When the device took `run` up: when its launch returned, or when
        the tick before it was done if that came later."""
        return max(run.launched_at, self.free_at)

    def done(self, run: _Launched, seen_at: float):
        """`run` was seen done at `seen_at` (a wait on it returned, or ONE
        `np.asarray` of it): when the device was done with it and free for
        the next, and its program's device time."""
        run.done_at = seen_at - self.way_back_s
        self.device_s.setdefault(run.program, deque(maxlen=3)).append(
            run.done_at - self._started(run))
        self.free_at = run.done_at

    def hold_until(self, run: _Launched) -> Optional[float]:
        """The instant `run` (the newest launch, the tick before it done) is
        one lead from its end; None while its program's device time was
        never seen."""
        seen = self.device_s.get(run.program)
        if seen is None:
            return None
        return self._started(run) + min(seen) - self.lead_s()

    def until(self, fetch, deadline: float) -> bool:
        """Give the core away until `deadline`, or hold it to the moment
        `fetch` is found ready if that comes first (True). ONE sleep, cut
        short by twice what such sleeps overran, where that leaves a sleep
        this host can give; then a spin: a sleep that overruns leaves the
        chip idle, a spin costs the core for that stretch alone."""
        clock = self.clock
        while not fetch.is_ready():
            now = clock()
            if now >= deadline:
                return False
            nap = deadline - now - 2 * self.oversleep.mean
            if nap >= self.nap_floor_s:
                self.sleep(nap)
                self.oversleep.add(clock() - now - nap)
        return True

    def wait_for(self, run: _Launched):
        """Until `run` is done on the device (at once if it was seen so)."""
        if run.done_at is None:
            run.fetch.block_until_ready()
            self.done(run, self.clock())

    def read(self, fetch) -> Tuple[np.ndarray, bool]:
        """The ids of a tick that is done, and whether the read found them
        on the host (it returned within `FOUND_WITHIN_S`) or had to wait
        for their copy."""
        t = self.clock()
        ids = np.asarray(fetch)
        return ids, self.clock() - t < self.FOUND_WITHIN_S


class ContinuousBatchingEngine:
    """Slot-scheduled decode loop: one compiled tick, S independent
    sequences in flight, admission the tick a slot frees.

    Weights are shared BY NAME with a `transformer_lm` train graph (train
    or load into `scope` first, then hand the same scope here); absent
    parameters are initialized by this engine's own startup program, so a
    fresh engine also runs standalone (random weights — tests, benches).
    """

    #: one EAGER tick in this many realizes its ids in two parts, under
    #: `engine/device_wait` and `engine/copy_back` (two parts cost the thread
    #: a second sleep and wake-up in front of a first token, 0.08 ms on a TPU
    #: host: PERF.md section 6, PR 41). A tick read late has the two parts by
    #: construction, both after the next launch, so the medians have every
    #: one of those
    WAIT_SPLIT_EVERY = 16

    #: how a prompt is consumed (`stats()["prefill"]`): one token a tick
    #: here; the paged engine says "chunked" when it builds its mixed tick
    prefill = "one_token"

    def __init__(self, n_slots: int = 8, vocab: int = 32000,
                 max_len: int = 64, d_model: int = 512, d_inner: int = 2048,
                 num_heads: int = 8, num_layers: int = 6,
                 dropout: float = 0.0, packed: bool = False,
                 eos_id: Optional[int] = None, scope=None,
                 policy: str = "continuous",
                 cache_prefix: Optional[str] = None,
                 quant: Optional[str] = None,
                 speculative=None):
        from ..core import unique_name
        from ..framework.executor import Executor
        from ..framework.program import Program, program_guard
        from ..framework.scope import global_scope

        enforce(policy in ("continuous", "static"),
                f"unknown scheduling policy {policy!r}",
                exc=InvalidArgumentError)
        if cache_prefix is None:
            # per-engine cache namespace: two engines sharing one scope
            # (e.g. both over the same trained weights) must not alias
            # each other's slot caches — shapes differ with n_slots
            cache_prefix = f"srv{next(_ENGINE_SEQ)}"
        self.policy = policy
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        #: the model dims + cache namespace, which every program builder
        #: hook reads (`**self._builder_dims`): the speculative
        #: draft/verify ticks and the mixed tick must match the main
        #: tick's architecture and share its cache names
        self._cache_prefix = cache_prefix
        self._builder_dims = dict(
            vocab=vocab, d_model=d_model, d_inner=d_inner,
            num_heads=num_heads, num_layers=num_layers, dropout=dropout,
            packed=packed)
        self._slots = SlotAllocator(n_slots)
        self._active: Dict[int, GenRequest] = {}      # slot -> request
        self._pending: "deque[GenRequest]" = deque()
        self._lock = threading.Lock()
        self._rid = 0
        #: the exception a tick raised (`fail_all`); a failed engine
        #: refuses new work — its donated cache state is gone
        self.failed: Optional[BaseException] = None

        self._program, self._startup = Program(), Program()
        with program_guard(self._program, self._startup), \
                unique_name.guard():
            self._build_tick_program()
        self.scope = scope or global_scope()
        self._exe = Executor()
        self._init_missing_vars(self._startup)
        # speculative decoding (serving/speculative.py): the draft model
        # COPIES the target's f32 weights under the reserved `draft_`
        # prefix, so it must be built BEFORE the target quantize pass
        # erases the f32 payloads; its prepared steps bind in
        # `spec.finalize()` after the main step is bound below
        self.spec = None
        if speculative is not None and speculative is not False:
            from .speculative import SpeculativeDecoder
            self.spec = SpeculativeDecoder(self, speculative)
            self.spec.build_draft()
        # weight-only quantized serving (quant='int8'/'int4'): rewrite the
        # tick program's persistable f32 weights into block-scaled
        # (payload, scales) pairs BEFORE the step is prepared. The freed
        # f32 bytes (quant_freed_bytes) are KV headroom: at a fixed HBM
        # budget they buy extra BlockPool blocks on the paged engine
        # (tests/test_quant_serving.py holds the freed bytes to the count).
        # Kill switch PTPU_QUANT_PARAMS=0 serves f32 regardless of `quant`.
        enforce(quant in (None, "int8", "int4"),
                f"quant must be None, 'int8' or 'int4', got {quant!r}",
                exc=InvalidArgumentError)
        self.quant = None
        self.params_bytes_f32 = self._param_bytes()
        self.quant_freed_bytes = 0
        if quant is not None:
            from ..core import flags as _flags
            if _flags.get_flag("quant_params"):
                from ..framework.passes import get_pass
                get_pass("quantize_params_pass",
                         bits=8 if quant == "int8" else 4)(
                    self._program, self.scope)
                self.quant = quant
                self.params_bytes_quantized = self._param_bytes()
                self.quant_freed_bytes = (self.params_bytes_f32
                                          - self.params_bytes_quantized)
        #: counts `_fill_tick_feeds` takes where it walks the feeds anyway;
        #: they ride the `engine/tick` span (the paged engine: `kv_blocks`)
        self._tick_attrs: Dict[str, int] = {}
        self._feeds = _feed_arrays(self._program)
        # zero-dispatch steady state: the prepared step is BOUND — argument
        # tuples are built once here, never per tick — and bind() lays the
        # feeds out in the one host buffer a launch transfers: from here
        # on `_feeds` holds the views of it that the fills write in place
        # (PreparedStep.bind)
        self._step = self._exe.prepare(
            self._program, dict(self._feeds), self._tick_fetches(),
            self.scope, name="decode_tick").bind(self._feeds)
        self._tok = self._feeds["tick_tok"]
        self._pos = self._feeds["tick_pos"]
        self._from_last = self._feeds["tick_from_last"]
        #: the bound steps by the name `_run_bound_step` knows them under
        #: (`stats()["dispatch"]`; `_target_state_owner` names the one
        #: that ran last)
        self._bound_steps = {"main": self._step}
        # which bound step's held rw tuple points at the LIVE target
        # caches: "main" (the plain tick) or "verify" (the speculative
        # verify forward). The two share the donated cache buffers, so
        # whichever runs after the other refreshes first
        # (PreparedStep.refresh_state); pure steady states never refresh.
        self._target_state_owner = "main"
        #: the tick whose ids are still on the device, and which may still
        #: be running there (`_Launched`). `_plain_tick` waits for it, reads
        #: and commits it after the next launch
        self._uncommitted: Optional[_Launched] = None
        #: when a late tick's `step()` returns (`_plain_tick`, the hold)
        self._pacer = _TickPacer(way_back_s=seen_done_lag_s())
        #: ticks whose ids were read a launch late, ticks launched while
        #: the tick before was still on the device, and reads behind a wait
        #: that found the ids on the host (`stats()["dispatch"]`)
        self.late_reads = 0
        self.run_ahead = 0
        self.copies_found = 0
        # census counters (`stats()`: occupancy)
        self.n_ticks = 0
        self.busy_slot_ticks = 0
        self.total_slot_ticks = 0
        self.tokens_out = 0
        #: TARGET-model forwards executed (plain ticks + verify
        #: forwards): the denominator of tokens-per-target-forward — the
        #: speculative amortization headline (`stats()["speculative"]`)
        self.target_forwards = 0
        self._started_at = time.time()
        #: wall time of the last executed decode tick (None before the
        #: first) — /healthz reports its age as the liveness signal
        self.last_tick_at: Optional[float] = None
        #: completed requests, newest last (bounded) — the per-request
        #: latency decomposition record (`GenRequest`'s four phases)
        self.completed_log: "deque[GenRequest]" = deque(maxlen=512)
        self._init_metrics()
        # the slot KV caches are persistable fixed-shape state: their
        # byte census is pinned at construction. Seed the process-wide
        # kv watermark (ptpu_memory_kv_cache_bytes) now so a scrape or a
        # dossier taken before the first tick already carries it; ticks
        # re-stamp it (two engines in one process: last writer wins the
        # `current`, the peak ratchets over both)
        self._kv_bytes_static = self._kv_cache_bytes()
        # per-token KV bytes across every layer cache: what ONE occupied
        # position costs — the unit of the used-vs-reserved split
        self._kv_bytes_per_token = (self._kv_bytes_static
                                    / max(n_slots * max_len, 1))
        self._stamp_kv_watermarks({})
        if self.spec is not None:
            # builds + quantizes the verify program (twin of the main
            # tick — same resident payloads), binds both spec steps,
            # registers the spec gauges
            self.spec.finalize()
        #: may a tick's ids be read a launch late (`_plain_tick`)? Not in an
        #: engine built with something that reads or moves a tick's results
        #: between ticks
        self._late_ok = not self._commits_every_tick()

    # -- tick-program construction (overridden by PagedKVEngine) ----------
    def _build_tick_program(self):
        """Build the compiled tick into the current default programs; must
        set `self._next_ids` (the [S,1] int64 fetch) and
        `self.cache_names` (the persistable KV state var names)."""
        from ..models import transformer
        self._next_ids, self.cache_names = \
            transformer.transformer_lm_decode_tick(
                n_slots=self.n_slots, max_len=self.max_len,
                cache_prefix=self._cache_prefix, **self._builder_dims)

    def _tick_fetches(self):
        return [self._next_ids]

    def _fill_tick_feeds(self, active: Dict[int, "GenRequest"]):
        tok, pos, from_last = self._tok, self._pos, self._from_last
        tok[:] = 0
        pos[:] = 0.0
        from_last[:] = 0
        for slot, req in active.items():
            if req.next_tok is None:     # the last tick left it on the device
                from_last[slot, 0] = 1
            else:
                tok[slot, 0] = req.next_tok
            pos[slot, 0, 0] = float(req.fed)

    def _stamp_kv_watermarks(self, active: Dict[int, "GenRequest"]):
        """The used-vs-reserved split (ISSUE r20 satellite): reserved is
        the engine's whole KV footprint (slot engine: every slot's full
        max_len row, pinned at construction), used is the positions
        live requests actually occupy — the gap between the two gauges
        IS the per-slot reservation waste paging reclaims."""
        used = sum(min(r.fed, self.max_len) for r in active.values()) \
            * self._kv_bytes_per_token
        _obs_memory.update_watermark("kv_cache_bytes",
                                     self._kv_bytes_static)
        _obs_memory.update_watermark("kv_cache_used_bytes", used)

    def _init_metrics(self):
        """Per-engine MetricsRegistry (observability/metrics.py) — the
        serving telemetry EngineServer exposes over HTTP /metrics and the
        ROADMAP-item-3 load harness scrapes: tokens/s, queue depth, slot
        occupancy, tick-latency quantiles, KV-cache bytes."""
        r = self.metrics_registry = _obs_metrics.MetricsRegistry()
        self._m_tokens = r.counter(
            "ptpu_engine_tokens_total", "Tokens sampled by the engine.")
        self._m_ticks = r.counter(
            "ptpu_engine_ticks_total", "Decode ticks executed.")
        self._m_completed = r.counter(
            "ptpu_engine_requests_completed_total", "Completed requests.")
        r.gauge("ptpu_engine_queue_depth",
                "Requests waiting for a slot.", fn=lambda: self.n_pending)
        r.gauge("ptpu_engine_active_slots",
                "Slots carrying an in-flight request.",
                fn=lambda: self.n_active)
        r.gauge("ptpu_engine_slot_occupancy",
                "Fraction of slot-ticks that carried a request.",
                fn=self.occupancy)
        r.gauge("ptpu_engine_kv_cache_bytes",
                "Bytes held by the slot-indexed KV caches.",
                fn=self._kv_cache_bytes)
        r.gauge("ptpu_engine_tokens_per_second",
                "Tokens sampled per wall second since engine start.",
                fn=lambda: (self.tokens_out
                            / max(time.time() - self._started_at, 1e-9)))
        self._m_tick_latency = r.histogram(
            "ptpu_engine_tick_latency_seconds",
            "Wall latency of one decode tick.",
            buckets=TICK_LATENCY_BUCKETS)
        self._m_dispatch = r.histogram(
            "ptpu_engine_dispatch_seconds",
            "Host-side dispatch share of one decode tick: feed fill + "
            "bound-call argument handling up to the async-dispatch "
            "return, excluding the realization barrier (device wait).",
            buckets=(1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3,
                     2.5e-3, 5e-3, 1e-2, 2.5e-2))
        for q, name in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            r.gauge(f"ptpu_engine_tick_latency_{name}_seconds",
                    f"{name} decode-tick latency (histogram estimate).",
                    fn=(lambda q=q:
                        self._m_tick_latency.quantile(q) or 0.0))
        # per-request latency decomposition: one labeled histogram
        # family, phase=queue_wait|prefill|decode|transport, plus the
        # end-to-end series the phases must sum to
        req_buckets = (1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2,
                       2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                       10.0, 30.0)
        self._m_req_phase = {
            phase: r.histogram(
                "ptpu_request_latency_seconds",
                "Per-request latency decomposition by lifecycle phase.",
                labels={"phase": phase}, buckets=req_buckets)
            for phase in ("queue_wait", "prefill", "decode", "transport")}
        self._m_req_e2e = r.histogram(
            "ptpu_request_e2e_seconds",
            "End-to-end request latency (submit -> completion frame "
            "sent; -> completion when no server is attached).",
            buckets=req_buckets)

    def _param_bytes(self) -> int:
        """Resident bytes of the tick program's weight state (census
        categories params + params_quantized) — the before/after pair of
        the weight-only quantization claim."""
        from ..framework.costs import state_category
        seen, total = set(), 0
        for b in self._program.blocks:
            for name, v in b.vars.items():
                if name in seen or not v.persistable \
                        or not self.scope.has_var(name):
                    continue
                seen.add(name)
                if state_category(v, name) in ("params",
                                               "params_quantized"):
                    total += int(_obs_memory.per_device_bytes(
                        self.scope.get(name)))
        return total

    def _kv_cache_bytes(self) -> int:
        total = 0
        for name in self.cache_names:
            if not self.scope.has_var(name):
                continue
            v = self.scope.get(name)
            if hasattr(v, "dtype") and hasattr(v, "shape"):
                total += int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
        return total

    def _init_missing_vars(self, startup, aliases=None) -> List[str]:
        """Give the serving scope the variables of `startup` it lacks, and
        touch no other: only the startup ops that produce a missing
        variable run (`Program.prune`), so trained weights already present
        (shared by name) are neither re-randomized nor initialized a
        second time into a scope that is thrown away; caches and any
        untrained parameters get their init. A variable the weight
        quantization pass erased (its payload lives on as `@qparam`)
        counts as present. `aliases` maps a missing name to a resident
        one it shares a buffer with instead of being initialized (the
        speculative draft's weights). Returns the names it initialized."""
        from ..framework.scope import Scope
        scope = self.scope
        produced = [n for op in startup.global_block().ops
                    for n in op.output_names()]
        aliases = aliases or {}
        missing = []
        for name in dict.fromkeys(produced):
            if scope.has_var(name) or scope.has_var(name + "@qparam"):
                continue
            if name in aliases:
                scope.set_var(name, scope.get(aliases[name]))
            else:
                missing.append(name)
        if missing:
            pruned = startup.prune(missing)
            pruned.random_seed = startup.random_seed
            tmp = Scope()
            self._exe.run(pruned, scope=tmp)
            for name in missing:
                scope.set_var(name, tmp.get(name))
        return missing

    # -- request intake ---------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new: int,
               eos_id: Optional[int] = "engine",
               on_done: Optional[Callable] = None,
               request_id: Optional[str] = None,
               defer_transport: bool = False) -> GenRequest:
        """Queue a generation request; returns the GenRequest handle
        (wait() for completion, or pass on_done — called on the ENGINE
        thread, keep it cheap). `request_id` is the caller's correlation
        id (EngineClient threads it through the RPC frame); it rides
        every span and the completion frame — auto-minted when absent."""
        enforce(len(prompt) >= 1, "prompt must not be empty",
                exc=InvalidArgumentError)
        self._enforce_request_fits(prompt, max_new)
        with self._lock:
            if self.failed is not None:
                raise RuntimeError(
                    f"engine failed: {type(self.failed).__name__}: "
                    f"{self.failed}")
            self._rid += 1
            req = GenRequest(self._rid, prompt, max_new,
                             self.eos_id if eos_id == "engine" else eos_id,
                             on_done, request_id=request_id,
                             defer_transport=defer_transport)
            self._pending.append(req)
        return req

    def _enforce_request_fits(self, prompt, max_new):
        """The per-request length limit, NAMED for what it actually is
        (ISSUE r20 satellite): on the slot engine every request owns one
        fixed [max_len] KV row, so the row width is the hard cap. The
        paged engine overrides this — there the cap is the block table's
        span; pool capacity governs admission, not submission."""
        enforce(len(prompt) + int(max_new) <= self.max_len,
                f"prompt({len(prompt)}) + max_new({max_new}) exceeds the "
                f"slot engine's per-slot KV row width max_len="
                f"{self.max_len} (each slot reserves one full-length "
                f"row; use PagedKVEngine for pool-capacity-bound "
                f"admission)", exc=InvalidArgumentError)

    # -- scheduler --------------------------------------------------------
    def _admit_request(self, req: GenRequest) -> bool:
        """Admission-time resource acquisition beyond the slot itself.
        Called under the engine lock with a slot guaranteed free; True
        admits, False leaves the request pending (head-of-line wait —
        FIFO admission must not starve a big request behind small ones).
        The paged engine acquires the request's block table here."""
        return True

    def _release_request(self, req: GenRequest):
        """Completion-side resource release (called under the engine
        lock, paired with `_admit_request`). The paged engine returns
        the request's blocks to the pool / prefix cache here."""

    def _note_position_written(self, req: GenRequest, pos: int):
        """One cache position of `req` was written by the tick that just
        ran. The paged engine uses this to mark prefix blocks filled
        (sharable) the moment their last row lands."""

    def _note_tick_writes(self, active: Dict[int, "GenRequest"]):
        """Pre-dispatch hook naming the cache positions the imminent
        tick will write. The paged engine's shadow-state sanitizer
        (`PTPU_KV_SANITIZE=1`) checks each one against the ownership
        model here — a write into a shared or freed block raises its
        named diagnostic BEFORE the scatter runs. Default: no-op (the
        slot engine's per-slot rows cannot alias)."""

    # -- speculative-decoding hooks (overridden by PagedKVEngine) ---------
    def _build_verify_tick(self, gamma):
        """Build the verify program (γ+1-wide window forward over the
        TARGET's caches and weights, shared by name) into the current
        default programs; returns (ids, logp, cache_names)."""
        from ..models import transformer
        return transformer.transformer_lm_spec_verify_tick(
            n_slots=self.n_slots, gamma=gamma, max_len=self.max_len,
            cache_prefix=self._cache_prefix, **self._builder_dims)

    def _fill_verify_row(self, feeds, slot: int, req: GenRequest,
                         g: int):
        """Fill slot `slot`'s verify-feed rows for a window starting at
        `req.fed` (spec_tok is filled batch-wide by the caller)."""
        feeds["spec_pos"][slot, 0, 0] = float(req.fed)

    def _spec_capable(self, req: GenRequest, g: int) -> bool:
        """Can `req` take a full γ+1 window without overrunning its KV
        span? A single ineligible slot degrades the whole step to one
        plain tick (mixed windows aren't worth a second compiled
        shape)."""
        return req.fed + g <= self.max_len

    def _spec_rollback(self, req: GenRequest, keep_len: int,
                       written_len: int) -> int:
        """Positions [keep_len, written_len) of `req` were written by a
        verify forward but rejected. Slot engine: a no-op — the stale
        rows sit above the slot's position mask and are rewritten before
        they are ever exposed (the same write-before-expose argument as
        slot reuse). The paged engine rolls fully-dead blocks back
        through the pager. Returns the number of blocks rolled back."""
        return 0

    def _admit_pool_attrs(self) -> Dict[str, int]:
        """What `engine/admit` says of the resource admission waits on,
        read after the admissions: the paged engine's pool level. The
        slot engine's only resource is the slot."""
        return {}

    def _admit(self):
        admitted = []
        with _tracing.span("admission", "engine/admit",
                           pending=len(self._pending)) as sp, self._lock:
            if self.policy == "static" and (self._active
                                            or not self._pending):
                return
            while self._pending:
                if self.policy == "static" and \
                        self._slots.n_free == 0:
                    break
                if self.policy == "continuous" and \
                        self._slots.n_free == 0:
                    break
                if not self._admit_request(self._pending[0]):
                    break
                slot = self._slots.alloc()
                req = self._pending.popleft()
                req.slot = slot
                req.admitted_at = time.time()
                req.admitted_pc = time.perf_counter()
                req.admitted_tick = self.n_ticks
                self._active[slot] = req
                admitted.append(req)
            if _tracing.enabled():
                # counted here, where admission happens: the prompt tokens
                # taken on, and how many of them the prefix cache already
                # held (trace provenance only: not summed when tracing is
                # off)
                sp.attrs.update(
                    admitted=len(admitted),
                    prompt_tokens=sum(len(r.prompt) for r in admitted),
                    shared_tokens=sum(r.shared_len for r in admitted),
                    **self._admit_pool_attrs())
        for req in admitted:
            # the queue-wait phase becomes a first-class span the moment
            # it ends (slot assignment) — retroactive, exact boundaries
            _tracing.record_span(
                "request", "request/queue_wait", req.submitted_pc,
                req.admitted_pc, request_id=req.request_id,
                slot=req.slot)
            self._m_req_phase["queue_wait"].observe(
                req.admitted_pc - req.submitted_pc)

    @property
    def n_active(self) -> int:
        with self._lock:
            return len(self._active)

    @property
    def n_pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def _commits_every_tick(self) -> bool:
        """Was the engine built with something that needs a tick's ids (or
        its blocks) before the next launch? A speculative round reads its
        ids inside the round; the paged engine adds its own. Such an engine
        commits every tick before `step()` returns."""
        return self.spec is not None

    def _advance_position(self, req: GenRequest) -> bool:
        """The half of a slot's commit that needs no ids: `req` consumed
        one position. Returns True when that position's output is a
        sampled token (`_emit_token`'s), False while the prompt goes on."""
        k = req.fed                    # the position just consumed
        req.fed += 1
        self._note_position_written(req, k)
        if k < len(req.prompt) - 1:
            req.next_tok = req.prompt[k + 1]     # still prefilling
            return False
        return True

    def _advance_slot(self, req: GenRequest, out_id: int) -> bool:
        """Advance `req` one position with the model's output `out_id`
        for that position — the per-slot commit shared by the plain tick
        and every speculative verify position (identical phase stamps and
        finish semantics by construction). Returns True when the request
        just finished (max_new / eos / out of room)."""
        return self._advance_position(req) and self._emit_token(req, out_id)

    def _emit_token(self, req: GenRequest, out_id: int) -> bool:
        """`out_id` is the token sampled after `req`'s last consumed
        position: stamp the first one, record it, make it the next fed
        token. Returns True when the request just finished."""
        t = int(out_id)                          # sampled next token
        if req.first_token_at is None:
            req.first_token_at = time.time()
            req.first_token_pc = time.perf_counter()
            # the tick that emits it is counted already (a speculative
            # round is counted after it emits: `step` adds its one)
            req.ticks_to_first = self.n_ticks - req.admitted_tick
        req.tokens.append(t)
        self.tokens_out += 1
        self._m_tokens.inc()
        req.next_tok = t
        hit_eos = (req.eos_id is not None and t == req.eos_id)
        return self._ends_by_count(req, 0) or hit_eos

    def _ends_by_count(self, req: GenRequest, unread: int = 1) -> bool:
        """Does `req` end with the position it consumed last, whatever the
        token: `max_new` reached or out of room? `unread`: the sampled
        tokens of it that are not in `req.tokens` yet."""
        return (len(req.tokens) + unread >= req.max_new
                or req.fed >= self.max_len)

    def step(self) -> List[GenRequest]:
        """One decode step: admit, run, collect. Returns the requests whose
        completion this step DELIVERED. A no-op (returns []) when nothing is
        active or pending. Without speculation (or when any active
        request is too close to its length cap to take a full window)
        this is one plain tick, recorded as a "tick" span and observed
        into the tick-latency histogram; with `speculative=` it is one
        speculative round (γ+1 draft ticks + one verify forward —
        `speculate`/`verify` spans) advancing every slot up to γ+1
        positions.

        A plain tick whose ids nobody waits for is read a launch LATE
        (`_plain_tick`): its tokens, and the completions among them, come
        out of the next `step()`, and this one returns while the tick is
        still on the device, one launch's own time before its end. The
        step that leaves the engine idle always delivers its own."""
        self._pacer.entered()
        self._admit()
        with self._lock:
            # a closed request holds its slot for its completion alone
            active = {s: r for s, r in self._active.items() if not r.closed}
        if not active:
            return []
        if self.spec is None or not all(
                self._spec_capable(r, self.spec.cfg.gamma + 1)
                for r in active.values()):
            return self._plain_tick(active)
        no_token = [r for r in active.values() if r.first_token_pc is None]
        finished = self.spec.round(active)
        with _tracing.span("tick", "engine/commit"):
            self._m_ticks.inc()
            self.n_ticks += 1
            for r in no_token:     # the round itself, counted just now
                if r.first_token_pc is not None:
                    r.ticks_to_first += 1
            self.last_tick_at = time.time()
            self._stamp_kv_watermarks(active)
            self.busy_slot_ticks += len(active)
            self.total_slot_ticks += self.n_slots
        self._finish(finished)
        return finished

    def _finish(self, finished: List[GenRequest]):
        """Deliver the completions of `finished` and give up their slots
        (and what `_release_request` holds for them)."""
        if not finished:
            return
        with _tracing.span("request", "engine/finish"):
            # complete (firing on_done -> writer.offer) BEFORE dropping
            # the request from _active: a drain poll reading
            # n_active==0 must imply every completion frame is already
            # in its writer queue, or the drain could close the writer
            # ahead of the final frame and silently drop it
            for req in finished:
                req._complete()
            with self._lock:
                for req in finished:
                    del self._active[req.slot]
                    self._slots.free(req.slot)
                    self._release_request(req)
            self._m_completed.inc(len(finished))
            for req in finished:
                self._finalize_request(req)

    def _pre_tick(self, active: Dict[int, "GenRequest"]
                  ) -> Dict[int, "GenRequest"]:
        """Scheduler hook run at the top of every plain tick, before the
        feeds fill: the two-tier offload engine (serving/kv_pager.py,
        `host_tier=`) resumes/suspends requests here — swapping KV
        blocks against the host tier between ticks — and returns the
        RESIDENT subset that actually ticks. Default: everything
        admitted is resident. An override that does work opens
        `engine/pre_tick` around it."""
        return active

    def _plain_tick(self, active: Dict[int, "GenRequest"]
                    ) -> List[GenRequest]:
        """Fill, launch (the ids' copy to the host enqueued right behind
        it), read the tick before, commit, hold: one tick. What
        the host does with a tick's results is in two halves. The POSITIONS
        (`_advance_positions`: `fed`, the blocks filled, who ends by count)
        need no ids and are applied after the launch, beside the device. The
        IDS (`_commit_ids`: tokens, the first token's stamp, `eos`,
        completions) are read before this returns on an EAGER tick, and on a
        LATE one (`_reads_late`) left on the device, where the next tick's
        decode rows take them (`_LastIds`, models/transformer.py), and read
        and committed after that next launch.

        A late tick is not waited for either: this returns while it runs,
        one launch's own time before the device is done with it
        (`_TickPacer`: the hold), so the next tick is launched BEHIND it, the
        device goes from one into the next, and the wait for its end
        (`engine/device_wait`) comes after that next launch. Whoever polls
        arrivals between steps still gets them into the very next tick, but
        for the last stretch of a tick, one launch long. When this returns at
        most one launched tick is unread, and the tick before it is read:
        never is a tick launched behind one that has not started. Returns
        the requests whose completion it delivered."""
        span = _tracing.span
        pacer = self._pacer
        t0 = time.perf_counter()
        with span("tick", "engine/tick") as tick:
            with span("dispatch", "engine/dispatch"):
                active = self._pre_tick(active)
                with span("dispatch", "engine/fill_feeds"):
                    self._fill_tick_feeds(active)
                    self._note_tick_writes(active)
                with span("dispatch", "engine/launch") as launch:
                    fetches = self._launch_tick()
                    # the ids' copy to the host is enqueued WITH the tick,
                    # so no host thread stands in its way: the read (behind
                    # the next launch on a late tick, at once on an eager
                    # one) finds the bytes on the host or waits for a copy
                    # that is on its way (a TPU's runtime starts it once ITS
                    # host side has seen the tick done: docs/serving.md)
                    fetches[0].copy_to_host_async()
                    self.target_forwards += 1
                    launch.attrs["host_args"] = self._bound_steps[
                        self._target_state_owner].host_args
                # async dispatch returned
                run = pacer.launched(tick, fetches[0],
                                     self._target_state_owner)
            # the tick before, if its ids were left on the device: was it
            # still running when this one was queued behind it?
            before, self._uncommitted = self._uncommitted, None
            ahead = before is not None and not before.fetch.is_ready()
            self.run_ahead += ahead
            if _tracing.enabled():
                # counted here, in the scheduler, while the device runs: a
                # slot PREFILLS on this tick when the position it fed is a
                # prompt token whose output is dropped; the others decode.
                # Trace provenance only, like the rid list: neither is
                # built when tracing is off (the decode loop is the hot
                # path)
                tick.attrs.update(
                    self._tick_attrs, active=len(active),
                    request_ids=[r.request_id for r in active.values()])
                if "prefill" not in tick.attrs:   # else: the lanes filled
                    tick.attrs["prefill"] = sum(
                        1 for r in active.values()
                        if r.fed < len(r.prompt) - 1)
            if before is not None:
                # its end, with this tick queued behind it: the device goes
                # from one into the other, whenever the thread wakes up; then
                # the ids' way back, beside the device
                with span("tick", "engine/wait"):
                    with span("tick", "engine/device_wait"):
                        pacer.wait_for(before)
                    ids = self._read_ids(before.fetch)
            with span("tick", "engine/commit"):
                delivered: List[GenRequest] = []
                if before is not None:
                    self._note_tick_counts(before.tick, ids)
                    delivered = self._commit_ids(before.emits, ids)
                    for req in delivered:
                        # whose `eos` came out just now has a row in the tick
                        # in flight: `_advance_positions` drops it
                        req.closed = True
                self._stamp_kv_watermarks(active)
                run.emits = self._advance_positions(active)
                late = self._reads_late(active, run.emits)
            self._finish(delivered)
            tick.attrs["late"] = int(late)
            tick.attrs["ahead"] = int(ahead)
            with span("tick", "engine/wait"):
                # every stretch in which the thread waits for the device is
                # in here. A late tick is HELD until it is one launch from
                # its end (at once where it is found done; to its end, today
                # as before, while its program's device time was never
                # seen). An eager one needs its ids now: ONE `np.asarray`,
                # and on a sampled tick the same in its two parts (the wait
                # for the tick, then what is left of the ids' way back: their
                # copy was enqueued at the launch, like every tick's)
                if late:
                    target = pacer.hold_until(run)
                    if target is None:
                        with span("tick", "engine/device_wait"):
                            pacer.wait_for(run)
                    else:
                        with span("tick", "engine/hold") as hold:
                            early = pacer.until(run.fetch, target)
                            if early:
                                pacer.done(run, pacer.clock())
                            hold.attrs["early"] = int(early)
                    pacer.left()        # the lead runs from here
                elif (self.n_ticks % self.WAIT_SPLIT_EVERY == 0
                        and _tracing.enabled()):
                    with span("tick", "engine/device_wait"):
                        pacer.wait_for(run)
                    ids = self._read_ids(fetches[0])
                else:
                    ids = np.asarray(fetches[0])
                    pacer.done(run, pacer.clock())
            if not late:
                self._note_tick_counts(tick, ids)
        with span("tick", "engine/commit"):
            self._m_dispatch.observe(run.launched_at - t0)
            self._m_tick_latency.observe(time.perf_counter() - t0)
            self._m_ticks.inc()
            self.n_ticks += 1
            self.last_tick_at = time.time()
            self.busy_slot_ticks += len(active)
            self.total_slot_ticks += self.n_slots
            if late:
                self.late_reads += 1
                self._uncommitted = run
                finished = []
            else:
                finished = self._commit_ids(run.emits, ids)
        self._finish(finished)
        return delivered + finished

    def _read_ids(self, fetch) -> np.ndarray:
        """The ids of a tick that is done, under `engine/copy_back`: `found`
        1 where the copy started at the launch had brought them to the host
        already, 0 where the read had to wait for it."""
        with _tracing.span("tick", "engine/copy_back") as back:
            ids, found = self._pacer.read(fetch)
            back.attrs["found"] = int(found)
        self.copies_found += found
        return ids

    def _note_tick_counts(self, tick, ids: np.ndarray):
        """What the tick brought back behind its ids, onto ITS `engine/tick`
        span `tick` (still open on an eager tick, closed a launch ago on a
        late one: a span's attrs are its record's) and the engine's
        counters. Nothing here; the paged engine's routed layers count
        their rows."""

    def _launch_tick(self):
        """Launch the tick `_fill_tick_feeds` just filled; returns its
        fetches (device arrays: nothing waits here)."""
        return self._run_bound_step(self._step, "main")

    def _run_bound_step(self, step, owner: str):
        """Launch a bound step over the target's donated caches. Bound
        steps that share them (the plain tick, the paged engine's mixed
        tick, the speculative verify forward) each hold the buffers their
        own last call returned: whichever runs after another re-points
        itself at the live arrays first (`PreparedStep.refresh_state`, a
        dict probe a cache); a run of one step never refreshes."""
        if self._target_state_owner != owner:
            step.refresh_state()
            self._target_state_owner = owner
        return step.run_bound()                # zero-dispatch tick

    def _advance_positions(self, active: Dict[int, "GenRequest"]
                           ) -> List[tuple]:
        """The positional half of the commit of the tick just launched:
        every request that ticked consumed its position. Returns the
        (request, row of the tick's ids) pairs whose row is a sampled
        token. A closed request's row is dropped: its `eos` was read after
        the row was launched."""
        return [(req, slot) for slot, req in active.items()
                if not req.closed and self._advance_position(req)]

    def _reads_late(self, active: Dict[int, "GenRequest"],
                    emits: List[tuple]) -> bool:
        """Are the ids of the tick just launched left on the device, to be
        read after the NEXT launch? Exactly when nobody waits for them and
        a next tick is certain: none of `emits` is a request's first token,
        and some request of `active` goes on after this tick (the last tick
        before the engine idles is read at once). Then the requests of
        `emits` that go on take their next token from the device, and those
        that end by count are closed until their tokens are delivered."""
        if not self._late_ok or not all(req.tokens for req, _ in emits):
            return False
        ending = [req for req, _ in emits if self._ends_by_count(req)]
        if len(ending) == sum(not r.closed for r in active.values()):
            return False
        for req, _ in emits:
            req.next_tok = None
        for req in ending:
            req.closed = True
        return True

    def _commit_ids(self, emits: List[tuple], ids: np.ndarray
                    ) -> List[GenRequest]:
        """The half of a tick's commit that needs its ids: every request
        of `emits` takes its row's token; returns those that finished."""
        return [req for req, row in emits
                if self._emit_token(req, int(ids[row, 0]))]

    def _finalize_request(self, req: GenRequest):
        """Completion-side telemetry: the prefill/decode phase spans and
        histograms from the request's perf_counter stamps. The transport
        phase + end-to-end series land in `report_sent` when a server
        reports the completion frame on the wire; for a direct engine
        caller (no server → no wire) they are closed here with
        transport = 0, so the phase sums always match the e2e series."""
        first = req.first_token_pc if req.first_token_pc is not None \
            else req.done_pc
        _tracing.record_span("request", "request/prefill",
                             req.admitted_pc, first,
                             request_id=req.request_id, slot=req.slot,
                             prompt_len=len(req.prompt),
                             ticks=req.ticks_to_first,
                             lane_wait_ticks=req.lane_wait_ticks)
        _tracing.record_span("request", "request/decode", first,
                             req.done_pc, request_id=req.request_id,
                             slot=req.slot, new_tokens=len(req.tokens))
        ph = req.phases()
        self._m_req_phase["prefill"].observe(ph["prefill"])
        self._m_req_phase["decode"].observe(ph["decode"])
        self.completed_log.append(req)
        if not req.defer_transport:
            self._m_req_phase["transport"].observe(0.0)
            self._m_req_e2e.observe(req.e2e_s())

    def report_sent(self, req: GenRequest, sent_pc: float):
        """Server-side hook: the request's completion frame left the
        process at perf_counter time `sent_pc` (the _BatchingWriter
        on_sent callback). Closes the transport phase and the e2e
        series, and records the transport span."""
        req.sent_pc = float(sent_pc)
        req.sent_at = time.time()
        _tracing.record_span("request", "request/transport", req.done_pc,
                             req.sent_pc, request_id=req.request_id)
        self._m_req_phase["transport"].observe(req.sent_pc - req.done_pc)
        self._m_req_e2e.observe(req.e2e_s())

    def fail_all(self, exc: BaseException) -> List[GenRequest]:
        """A tick raised `exc` (compile failure, device memory exhausted
        at the first tick): the step's donated state is gone and the
        engine cannot continue. Every active and pending request
        completes NOW carrying `exc` — `wait()` raises, `on_done`
        callbacks see `req.error` — and later submits are refused, so no
        caller is left waiting on an engine that will never tick again."""
        with self._lock:
            self.failed = exc
            # the requests of a tick whose ids were never read are among
            # the active: a request leaves `_active` when it is delivered
            self._uncommitted = None
            reqs = list(self._active.values()) + list(self._pending)
            self._active.clear()
            self._pending.clear()
        for req in reqs:
            req.error = exc
            req._complete()
        return reqs

    def run_until_idle(self, max_ticks: Optional[int] = None
                       ) -> List[GenRequest]:
        """Tick until every pending/active request completed (or
        max_ticks); returns all completions in completion order."""
        done: List[GenRequest] = []
        ticks = 0
        while True:
            with self._lock:
                idle = not self._active and not self._pending
            if idle:
                return done
            done.extend(self.step())
            ticks += 1
            if max_ticks is not None and ticks >= max_ticks:
                return done

    def tick_hlo(self) -> str:
        """Optimized HLO text of the compiled decode tick
        (`PreparedStep.compiled_hlo`): shows which attention path the tick
        took at this engine's shape — a `tpu_custom_call` per layer when
        the fused decode kernel is in, none when the shape gate sent it
        to the composite."""
        return self._step.compiled_hlo()

    def occupancy(self) -> float:
        """Fraction of slot-ticks that carried an active request —
        continuous batching's object of optimization."""
        return (self.busy_slot_ticks / self.total_slot_ticks
                if self.total_slot_ticks else 0.0)

    def stats(self) -> Dict:
        """Instantaneous engine state for /healthz: slot/queue shape,
        tick liveness, token throughput."""
        now = time.time()
        return {
            "n_slots": self.n_slots,
            "active": self.n_active,
            "pending": self.n_pending,
            "ticks": self.n_ticks,
            "tokens_out": self.tokens_out,
            "occupancy": self.occupancy(),
            "last_tick_age_s": ((now - self.last_tick_at)
                                if self.last_tick_at is not None
                                else None),
            "uptime_s": now - self._started_at,
            # how a prompt is consumed: "one_token" a tick through the
            # decode rows, or "chunked" through the paged engine's lanes
            "prefill": self.prefill,
            "target_forwards": self.target_forwards,
            "tokens_per_target_forward": (
                self.tokens_out / max(self.target_forwards, 1)),
            "speculative": (self.spec.stats()
                            if self.spec is not None else None),
            # per bound step, the host arrays one launch hands over; the
            # ticks whose ids were read a launch late; those launched while
            # the tick before was still on the device; and the reads of a
            # tick seen done that found its ids on the host
            "dispatch": {**{name: {"host_args": step.host_args}
                            for name, step in self._bound_steps.items()},
                         "late_reads": self.late_reads,
                         "run_ahead": self.run_ahead,
                         "copies_found": self.copies_found},
        }


def _feed_arrays(program, share=()) -> Dict[str, np.ndarray]:
    """The feed arrays of a tick program, zeroed, made from the feeds its
    builder declared (`layers.data`), in declaration order: the builder is
    the one place that says a feed's name, shape and dtype. An engine
    makes them once and binds its prepared step to them; `bind` swaps each
    for the view of its span of the one buffer a launch transfers
    (declared shape, the dtype it has on the device), and the engine
    fills those in place every tick (the decode loop allocates nothing).
    A name in `share` takes that array instead of a new one: a second
    program over the same feeds runs on what the first one's fill
    wrote."""
    share = dict(share)
    return {v.name: (share[v.name] if v.name in share
                     else np.zeros(v.shape, np.dtype(v.dtype)))
            for v in program.global_block().vars.values() if v.is_data}


# ---------------------------------------------------------------------------
# Prometheus /metrics exposition + /healthz
# ---------------------------------------------------------------------------


class _MetricsHTTPServer:
    """Minimal threading HTTP listener serving GET /metrics (Prometheus
    text exposition 0.0.4 from one registry — Multi or plain) and, when
    a `health_fn` is given, GET /healthz as structured JSON (the control
    loop's signal: engine serving/draining state, last-tick age, pending
    checkpoints, supervisor restart count)."""

    def __init__(self, addr, registry, health_fn=None):
        import http.server
        import json as _json

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server contract)
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    body = registry.expose().encode()
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                    code = 200
                elif path == "/healthz" and health_fn is not None:
                    health = health_fn()
                    body = _json.dumps(health, default=str).encode()
                    ctype = "application/json"
                    # draining surfaces as 503: a load balancer must stop
                    # routing to a replica that stopped admitting
                    code = 200 if health.get("status") == "serving" \
                        else 503
                else:
                    self.send_error(404, "serving /metrics and /healthz")
                    return
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):   # scrapes must not spam stderr
                pass

        self._srv = http.server.ThreadingHTTPServer(addr, Handler)
        self._srv.daemon_threads = True
        self.server_address = self._srv.server_address

    def serve_forever(self):
        self._srv.serve_forever(poll_interval=0.1)

    def shutdown(self):
        self._srv.shutdown()

    def server_close(self):
        self._srv.server_close()


def scrape_metrics(host: str, port: int, timeout: float = 5.0) -> str:
    """One GET /metrics against an EngineServer's metrics address —
    what run_ci.sh and the tests use; production scrapers point Prometheus
    at the same URL."""
    import urllib.request
    with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=timeout) as resp:
        return resp.read().decode()


def scrape_healthz(host: str, port: int, timeout: float = 5.0) -> Dict:
    """One GET /healthz (same listener as /metrics): the parsed JSON
    health document. A draining server answers 503 but still carries the
    body — this helper returns it either way."""
    import json as _json
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=timeout) as resp:
            return _json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        if e.code == 503:   # draining: the body IS the health document
            return _json.loads(e.read().decode())
        raise


# ---------------------------------------------------------------------------
# generation RPC over the serving.py v2 transport
# ---------------------------------------------------------------------------


class EngineServer:
    """Serve a ContinuousBatchingEngine over TCP.

    Wire format is the serving.py framing with JSON-only frames:
      request   {"gen": {"prompt": [ids...], "max_new": n, "tag": any}}
      response  {"done": {"tag": any, "tokens": [ids...],
                          "latency_ms": float}}
    Responses are keyed by the client's `tag` (completion order is the
    ENGINE's order, not request order — short requests overtake long
    ones; that reordering is continuous batching working as designed).

    Threads: one engine thread ticks the decode loop; per connection, a
    reader admits requests and a writer flushes completions — completions
    landing on the same tick leave in one vectored send (serving.py
    `_sendall_vec`), so socket I/O and the decode tick overlap."""

    def __init__(self, engine: ContinuousBatchingEngine,
                 host: str = "127.0.0.1", port: int = 0,
                 metrics_port: Optional[int] = 0):
        import socket as _socket

        self.engine = engine
        self._sock = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
        self._sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.address = self._sock.getsockname()
        self._stop = threading.Event()
        self._wake = threading.Event()     # submissions kick the engine
        self._draining = threading.Event()  # admit nothing new, finish rest
        self._threads: List[threading.Thread] = []
        self._conns: List = []
        self._writers: List = []
        self._lock = threading.Lock()
        self._prev_sigterm = None
        #: "Type: message" of the exception that killed the engine thread
        #: (`_engine_failed`); /healthz reports it under status "failed"
        self.error: Optional[str] = None
        # Prometheus exposition + health: a small HTTP listener serving
        # GET /metrics and GET /healthz. A SEPARATE socket from the
        # generation RPC (that one speaks the serving.py frame protocol;
        # an HTTP GET on it would misparse as a frame header). The
        # scraped registry is the UNION of the engine's own registry and
        # the process-wide default registry, so one scrape sees serving,
        # checkpoint (ptpu_ckpt_*), and training (ptpu_train_*) series.
        # metrics_port=None disables; 0 picks an ephemeral port
        # (self.metrics_address after construction).
        self._http = None
        self.metrics_address = None
        if metrics_port is not None:
            # materialize the process-wide series before the first
            # scrape: ptpu_ckpt_* and ptpu_train_* register lazily, and
            # a scrape must see the families (at zero) even before the
            # first save/step touches them
            from ..parallel import elastic as _elastic
            from ..trainer import training_metrics as _training_metrics
            _elastic.metrics_registry()
            _training_metrics()
            _obs_memory.memory_metrics()   # ptpu_memory_* + ptpu_mfu
            self._http = _MetricsHTTPServer(
                (host, metrics_port),
                _obs_metrics.MultiRegistry(
                    [engine.metrics_registry,
                     _obs_metrics.default_registry()]),
                health_fn=self.health)
            self.metrics_address = self._http.server_address

    def health(self) -> Dict:
        """The /healthz document — the control-loop signal (ROADMAP
        3(d)): admission state (serving vs draining after SIGTERM),
        engine tick liveness, pending async checkpoint commits, and the
        supervising process's restart count (PTPU_SUPERVISOR_RESTARTS,
        set by trainer.Supervisor for its children)."""
        from ..parallel import elastic as _elastic
        restarts = os.environ.get("PTPU_SUPERVISOR_RESTARTS")
        return {
            "status": ("failed" if self.error is not None
                       else "draining" if self._draining.is_set()
                       else "serving"),
            "error": self.error,
            "engine": self.engine.stats(),
            "checkpoints": {
                "pending_async": _elastic.pending_async_count()},
            "supervisor": {
                "restarts": int(restarts) if restarts else 0},
            # the memory board (r17): per-channel current + high-water
            # bytes and the last MFU reading — the same board every
            # flight-recorder dossier embeds, so live probing and
            # post-mortems read one vocabulary
            "memory": _obs_memory.watermark_board(),
            "pid": os.getpid(),
            "ts": time.time(),
        }

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "EngineServer":
        t = threading.Thread(target=self._engine_loop, daemon=True)
        a = threading.Thread(target=self._accept_loop, daemon=True)
        self._threads += [t, a]
        t.start()
        a.start()
        if self._http is not None:
            h = threading.Thread(target=self._http.serve_forever,
                                 daemon=True)
            self._threads.append(h)
            h.start()
            self._http_started = True
        return self

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown (the SIGTERM path): stop admitting — the
        listener closes and new `gen` frames on live connections are
        answered with a draining error — finish every in-flight AND
        already-queued request, flush the per-connection writer threads
        so every completion frame reaches its client, then shut down.
        Returns True when the engine fully drained within `timeout`
        (False: timed out; shutdown still ran, undelivered work was
        dropped)."""
        # flag flips under the admission lock: every reader thread either
        # observed draining (and rejects) or completed its submit before
        # this point (and the idle wait below sees that request) — no
        # window where a request is admitted into a stopping engine
        with self._lock:
            self._draining.set()
        # in-flight conns stay open so completions can still go out
        self._close_listener()
        deadline = None if timeout is None else time.time() + timeout
        drained = True
        while self.engine.n_active or self.engine.n_pending:
            self._wake.set()
            if deadline is not None and time.time() > deadline:
                drained = False
                break
            time.sleep(0.01)
        # flush writers BEFORE shutdown closes the sockets: close()
        # enqueues EOF and joins, so every queued completion frame is
        # vectored out first
        with self._lock:
            writers = list(self._writers)
        for w in writers:
            w.close()
        self.shutdown()
        return drained

    def install_sigterm_handler(self, exit_process: bool = True,
                                timeout: Optional[float] = None):
        """Wire SIGTERM to a graceful drain (main thread only — the
        signal module's contract). The handler returns immediately; a
        daemon thread performs the drain so the signal context never
        blocks, then — with exit_process — exits 0 (the k8s/preemption
        contract: SIGTERM means finish what you hold and leave
        cleanly)."""
        import signal as _signal

        def _handler(signum, frame):
            t = threading.Thread(target=self._drain_then_exit,
                                 args=(exit_process, timeout),
                                 daemon=True)
            t.start()

        self._prev_sigterm = _signal.signal(_signal.SIGTERM, _handler)
        return self

    def _drain_then_exit(self, exit_process: bool, timeout):
        try:
            self.drain(timeout=timeout)
            from ..parallel import elastic as _elastic
            # a co-resident elastic checkpoint writer must commit before
            # the process goes away (same drill as Trainer's
            # end-of-train flush)
            _elastic.wait_for_pending(timeout)
        except Exception as e:
            # a timed-out flush must not kill this thread BEFORE the
            # exit below: the SIGTERM disposition was replaced by our
            # handler, so skipping os._exit would leave a process that
            # ignores every further SIGTERM (undrainable zombie). The
            # exit-0 contract holds, but the failure must be visible —
            # operators need to tell a clean drain from a failed one
            from ..core import flags
            flags.vlog(0, "SIGTERM drain did not complete cleanly: "
                       "%s: %s (exiting anyway)", type(e).__name__, e)
        if exit_process:  # pragma: no cover - exits the interpreter
            os._exit(0)

    def shutdown(self):
        self._stop.set()
        self._wake.set()
        if self._http is not None:
            # socketserver's shutdown() blocks on an event only
            # serve_forever() ever sets — calling it when start() never
            # ran would hang forever; just close the listener then
            if getattr(self, "_http_started", False):
                self._http.shutdown()
            self._http.server_close()
        self._close_listener()
        import socket as _socket
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            # shutdown BEFORE close: reader threads parked in recv are
            # not woken by closing the fd on Linux; shutdown makes recv
            # return 0 immediately (same drill as PredictorServer)
            try:
                c.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        for t in self._threads:
            t.join(timeout=10)

    def _close_listener(self):
        from .transport import _close_listener
        _close_listener(self._sock)

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.shutdown()

    # -- engine thread ----------------------------------------------------
    def _engine_loop(self):
        try:
            while not self._stop.is_set():
                if self.engine.n_active or self.engine.n_pending:
                    self.engine.step()
                else:
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
        except Exception as e:
            # thread boundary: a tick that raises must reach every client
            # as an error frame — a daemon thread dying silently leaves
            # them blocked in recv forever
            self._engine_failed(e)

    def _engine_failed(self, exc: Exception):
        import traceback
        from ..core import flags
        self.error = f"{type(exc).__name__}: {exc}"
        flags.vlog(0, "engine thread failed, server stopping: %s\n%s",
                   self.error, traceback.format_exc())
        # same locked flip as drain(): a reader either sees the flag (and
        # rejects with the error) or finished its submit before fail_all
        with self._lock:
            self._draining.set()
        self.engine.fail_all(exc)
        self._stop.set()
        self._close_listener()

    # -- I/O threads ------------------------------------------------------
    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            import socket as _socket
            conn.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True)
            with self._lock:
                self._conns.append(conn)
                self._threads.append(t)
            t.start()

    def _serve_conn(self, conn):
        from .transport import _BatchingWriter, _encode_msg, _recv_msg

        # shared with PredictorServer: bounded queue + vectored batch
        # drain. Completions use the NON-blocking offer(): the engine
        # thread ticks for every connection and must never stall on one
        # that stopped reading — a client ~64 unread frames behind is
        # evicted (connection closed), frames for a dead connection are
        # dropped.
        writer = _BatchingWriter(conn)
        with self._lock:
            self._writers.append(writer)

        def on_done(req, tag):
            if req.error is not None:
                writer.offer(_encode_msg({
                    "error": f"engine failed: {type(req.error).__name__}: "
                             f"{req.error}", "tag": tag}))
                return
            ph = req.phases() or {}
            frame = _encode_msg({"done": {
                "tag": tag, "tokens": req.tokens,
                "request_id": req.request_id,
                "latency_ms": round(req.latency_s * 1e3, 3),
                "phases_ms": {k: round(v * 1e3, 3)
                              for k, v in ph.items()
                              if k != "transport"}}})
            # on_sent closes the transport phase: the writer thread
            # reports the perf_counter instant the vectored send
            # returned, and the engine observes transport + e2e. A
            # failed offer (dead writer / slow-consumer eviction) means
            # the frame will NEVER go out — close the series here so the
            # e2e count cannot lag the phase counts
            ok = writer.offer(frame, on_sent=(
                lambda ts, req=req: self.engine.report_sent(req, ts)))
            if not ok:
                self.engine.report_sent(req, time.perf_counter())

        try:
            while not self._stop.is_set():
                header, _ = _recv_msg(conn)
                if header is None or "gen" not in header:
                    break
                g = header["gen"]
                tag = g.get("tag")
                err = None
                admitted = False
                # check-and-submit under the admission lock (paired with
                # drain()'s locked flag flip): a submit can never slip in
                # after drain decided the engine is idle
                with self._lock:
                    if self.error is not None:
                        err = f"engine failed: {self.error}"
                    elif self._draining.is_set():
                        # graceful drain: in-flight work completes, but
                        # nothing new is admitted — the client gets an
                        # explicit rejection, never a silent drop
                        err = ("server draining (SIGTERM): not "
                               "admitting new requests")
                    else:
                        try:
                            self.engine.submit(
                                g["prompt"], g.get("max_new", 16),
                                on_done=(lambda req, tag=tag:
                                         on_done(req, tag)),
                                request_id=g.get("request_id"),
                                defer_transport=True)
                            admitted = True
                        except Exception as e:
                            err = f"{type(e).__name__}: {e}"
                if admitted:
                    self._wake.set()
                else:
                    # respond OUTSIDE the lock: it may block on writer
                    # backpressure
                    writer.respond(_encode_msg({"error": err,
                                                "tag": tag}))
        except (ConnectionError, OSError):
            pass
        finally:
            writer.close()
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)
                if writer in self._writers:
                    self._writers.remove(writer)


class EngineClient:
    """Client for EngineServer; supports pipelined generation requests."""

    def __init__(self, host: str, port: int):
        import socket as _socket

        self._sock = _socket.create_connection((host, port))
        self._sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        self._lock = threading.Lock()
        self._tag = 0

    def send_gen(self, prompt: Sequence[int], max_new: int = 16,
                 tag=None, request_id: Optional[str] = None):
        """`request_id` is the client's correlation id: it threads
        through admission, every decode tick's span attrs, the
        per-request latency decomposition, and comes back on the done
        frame — the end-to-end trace key across client/server/engine."""
        from .transport import _send_msg
        with self._lock:
            self._tag += 1
            tag = self._tag if tag is None else tag
            msg = {"gen": {"prompt": [int(t) for t in prompt],
                           "max_new": int(max_new), "tag": tag}}
            if request_id is not None:
                msg["gen"]["request_id"] = str(request_id)
            _send_msg(self._sock, msg)
        return tag

    def recv_done(self):
        """Next completion: (tag, tokens, latency_ms). Completion order is
        the engine's, not send order."""
        from .transport import _recv_msg
        header, _ = _recv_msg(self._sock)
        if header is None:
            raise ConnectionError("server closed the connection")
        if "error" in header:
            raise RuntimeError(f"server error: {header['error']}")
        d = header["done"]
        return d["tag"], d["tokens"], d["latency_ms"]

    def generate(self, prompt: Sequence[int], max_new: int = 16
                 ) -> List[int]:
        tag = self.send_gen(prompt, max_new)
        got_tag, tokens, _ = self.recv_done()
        if got_tag != tag:
            raise RuntimeError(
                f"unexpected completion tag {got_tag} (want {tag}); use "
                f"send_gen/recv_done for pipelined requests")
        return tokens

    def close(self):
        self._sock.close()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
