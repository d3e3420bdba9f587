"""Host-offload substrate: the pinned host pool + overlapped d2h/h2d
transfer stream that turn host RAM into a planned second memory tier
(ROADMAP item 5(a), ISSUE r23 tentpole).

The r20 paged KV pool, the r18 memory planner, and the ZeRO-1 reduce
mode all stop at the HBM boundary. The reference framework's pinned
host allocator + async memcpy streams (PAPER.md §L0/L1) make host
memory a first-class tier instead; "Memory-efficient array
redistribution through portable collective communication" (PAPERS.md)
is the framing — shards move between memory *tiers* with the same
planned-transfer discipline `reshard.py` uses between meshes. This
module is the shared substrate; three consumers ride it:

- **two-tier paged KV** (`serving/kv_pager.py`): `PagedKVEngine(
  host_tier=HostTierConfig(...))` spills cold requests' private blocks
  to the host pool and prefetches them back ahead of scheduled reads,
  so admitted concurrency at a fixed device pool-byte budget exceeds
  the r20/r21 device-only ceiling (tests/test_offload.py).
- **host-resident optimizer state** (`HostOptimizerState`, wired into
  `ParallelExecutor.run` behind `BuildStrategy.offload_optimizer_state`):
  ZeRO-1 accumulator shards live on host between steps and round-trip
  per step, priced by the `offload` section of `costs.predict` so the
  planner can refuse the mode when the PCIe transfer doesn't hide.
- **memory-plan stash tier** (`framework/memory_plan.py`): the
  remat-vs-stash search gains a stash-to-host alternative priced
  against the same `V5E_PCIE_BPS` roofline.

Three deliberate disciplines, inherited from earlier rounds:

- one accounting source (r17): every host-resident byte — KV spill,
  checkpoint staging (`elastic.save_train_state`), optimizer shards —
  goes through the ONE `shared_host_pool()` ledger, which publishes
  the `host_*_bytes` watermark channels. The census cannot
  double-count what a single ledger emits.
- exact wire census (r08/r11): `TransferStream` counts the actual
  bytes each job moves; tests/test_offload.py asserts predicted
  d2h/h2d bytes == these counters EXACTLY.
- named-diagnostic lint (r13): `check_schedule` turns a transfer
  scheduled after its read into the error-severity
  `offload-use-before-arrival` diagnostic (`tools/lint_program.py
  --offload`), with a mutation test per code.

CPU-mesh caveat, stated once here and repeated in every artifact that
prices the roofline: on this container's CPU backend "device" and
"host" are the same DRAM, so `np.asarray` (d2h) and `jnp.asarray`
(h2d) are memcpys, not PCIe DMA — transfer *overlap* is real (the
stream thread runs while the compute thread ticks; numpy releases the
GIL on large copies) but transfer *time* is not PCIe time. The
`V5E_PCIE_BPS` roofline prices the TPU case; measured cells carry an
explicit `cpu_mesh_caveat`.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..core.enforce import InvalidArgumentError, enforce

__all__ = [
    "HostTierConfig", "PinnedHostPool", "HostBuffer", "HostLease",
    "TransferStream", "TransferTicket", "shared_host_pool",
    "shared_stream", "HostOptimizerState", "optimizer_state_names",
    "TransferEvent", "prefetch_issue_tick", "kv_prefetch_events",
    "optimizer_roundtrip_events", "check_schedule", "offload_metrics",
    "offload_stats", "reset_offload",
]


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclass
class HostTierConfig:
    """Policy knobs for the two-tier paged KV cache.

    host_blocks        capacity of the host tier in KV blocks (the same
                       `block_size`-token pages the device BlockPool
                       holds). The pager enforces the two-pool identity
                       used_dev + used_host + free_dev + free_host ==
                       total over both tiers.
    prefetch_distance  start the h2d prefetch of a suspended request's
                       spilled blocks when the earliest projected
                       resume is this many ticks away (the issue tick
                       is `prefetch_issue_tick(read, distance)` — the
                       SAME helper `lint_program --offload` checks, so
                       the linted policy is the executed policy).
    rotate_quantum     anti-starvation: when a suspended request has
                       waited this many ticks with no capacity, evict
                       the resident request with the most remaining
                       work to host and hand its blocks over. 0
                       disables rotation (run-to-completion; suspended
                       requests resume only when a resident finishes).
    pin_index_nodes    prefix-sharing radix-index blocks never spill
                       (they are the highest-fanout bytes on the
                       device tier; evicting them trades one request's
                       latency for every sharer's).
    """
    host_blocks: int = 64
    prefetch_distance: int = 2
    rotate_quantum: int = 8
    pin_index_nodes: bool = True

    def __post_init__(self):
        enforce(self.host_blocks >= 1,
                f"HostTierConfig.host_blocks must be >= 1, got "
                f"{self.host_blocks}", exc=InvalidArgumentError)
        enforce(self.prefetch_distance >= 0,
                f"HostTierConfig.prefetch_distance must be >= 0, got "
                f"{self.prefetch_distance}", exc=InvalidArgumentError)
        enforce(self.rotate_quantum >= 0,
                f"HostTierConfig.rotate_quantum must be >= 0, got "
                f"{self.rotate_quantum}", exc=InvalidArgumentError)


# ---------------------------------------------------------------------------
# pinned host pool — the ONE host-byte ledger
# ---------------------------------------------------------------------------

#: ledger category -> watermark channel (observability/memory.CHANNELS).
#: `stash` has no live channel yet — the stash tier executes advisorily
#: on this backend (see memory_plan.search_remat) — but the category
#: still rows in `host_tier_rows()` so the census names the bytes.
_CATEGORY_CHANNEL = {
    "kv": "host_kv_bytes",
    "staging": "host_staging_bytes",
    "optimizer": "host_optimizer_bytes",
    "stash": None,
}


class HostBuffer:
    """One pool-owned host allocation (a numpy array standing in for a
    pinned-host region; on TPU this is where `pinned=True` would land)."""

    __slots__ = ("array", "category", "nbytes", "_freed")

    def __init__(self, array: np.ndarray, category: str):
        self.array = array
        self.category = category
        self.nbytes = int(array.nbytes)
        self._freed = False


class HostLease:
    """Accounting-only adoption of host bytes the caller already holds
    (e.g. `collect_chunks` staging in elastic.save_train_state): the
    bytes enter the pool ledger without a copy, and leave on
    `release()` (idempotent — the elastic writer threads release in
    `finally` blocks that can race a sync-path release)."""

    __slots__ = ("_pool", "nbytes", "category", "_released")

    def __init__(self, pool: "PinnedHostPool", nbytes: int, category: str):
        self._pool = pool
        self.nbytes = int(nbytes)
        self.category = category
        self._released = False

    def release(self):
        if not self._released:
            self._released = True
            self._pool._credit(self.category, -self.nbytes)


class PinnedHostPool:
    """The host-tier byte ledger + allocator. Every consumer of host
    RAM as a memory tier allocates (or leases) through here, so the
    `host_*_bytes` watermark channels, `host_tier_rows()` in the
    census, and /healthz all report from one accounting source
    (ISSUE r23 satellite 6: no double-count).

    `capacity_bytes == 0` means unbounded (the KV tier bounds itself
    in blocks via HostTierConfig; checkpoint staging is bounded by the
    snapshot size)."""

    def __init__(self, capacity_bytes: int = 0):
        enforce(capacity_bytes >= 0,
                f"PinnedHostPool capacity_bytes must be >= 0, got "
                f"{capacity_bytes}", exc=InvalidArgumentError)
        self.capacity_bytes = int(capacity_bytes)
        self._lock = threading.Lock()
        self._used: Dict[str, int] = {c: 0 for c in _CATEGORY_CHANNEL}
        self._peak_total = 0

    # -- accounting core ----------------------------------------------------

    def _credit(self, category: str, delta: int):
        enforce(category in _CATEGORY_CHANNEL,
                f"unknown host-pool category {category!r}; known: "
                f"{sorted(_CATEGORY_CHANNEL)}", exc=InvalidArgumentError)
        with self._lock:
            nv = self._used[category] + int(delta)
            enforce(nv >= 0,
                    f"host pool category {category!r} under-released: "
                    f"{self._used[category]} + {delta} < 0",
                    exc=InvalidArgumentError)
            total = sum(self._used.values()) + int(delta)
            if delta > 0 and self.capacity_bytes:
                enforce(total <= self.capacity_bytes,
                        f"host pool over capacity: {total} > "
                        f"{self.capacity_bytes} allocating {delta} "
                        f"bytes of {category!r}",
                        exc=InvalidArgumentError)
            self._used[category] = nv
            if total > self._peak_total:
                self._peak_total = total
            current = nv
        channel = _CATEGORY_CHANNEL[category]
        if channel is not None:
            from ..observability import memory as _memory
            _memory.update_watermark(channel, current)

    # -- allocation ---------------------------------------------------------

    def alloc(self, shape, dtype, category: str) -> HostBuffer:
        """A pool-owned host buffer; the ledger (and the category's
        watermark channel) moves before the caller sees the array."""
        arr = np.empty(shape, dtype=dtype)
        self._credit(category, int(arr.nbytes))
        return HostBuffer(arr, category)

    def free(self, buf: HostBuffer):
        if buf._freed:
            return
        buf._freed = True
        self._credit(buf.category, -buf.nbytes)

    def lease(self, nbytes: int, category: str) -> HostLease:
        """Adopt caller-held host bytes into the ledger (no copy)."""
        lease = HostLease(self, nbytes, category)
        self._credit(category, lease.nbytes)
        return lease

    # -- census surface -----------------------------------------------------

    def used_bytes(self, category: Optional[str] = None) -> int:
        with self._lock:
            if category is None:
                return sum(self._used.values())
            return self._used.get(category, 0)

    def rows(self) -> Dict[str, Any]:
        """The host-tier census rows `device_memory_census` embeds and
        the watermark board mirrors (one shape on both surfaces, r16/r17
        convention): per-category bytes + total + peak + capacity."""
        with self._lock:
            out: Dict[str, Any] = {
                f"host_{c}_bytes": int(v) for c, v in self._used.items()}
            out["host_total_bytes"] = int(sum(self._used.values()))
            out["host_peak_bytes"] = int(self._peak_total)
            out["capacity_bytes"] = int(self.capacity_bytes)
        return out


_shared_pool: Optional[PinnedHostPool] = None
_shared_pool_lock = threading.Lock()


def shared_host_pool() -> PinnedHostPool:
    """The process-wide host-tier ledger. KV spill, checkpoint staging
    and host-resident optimizer state all account here; tests reset it
    via `reset_offload()`."""
    global _shared_pool
    with _shared_pool_lock:
        if _shared_pool is None:
            _shared_pool = PinnedHostPool()
        return _shared_pool


# ---------------------------------------------------------------------------
# transfer stream — overlapped d2h/h2d with an exact byte census
# ---------------------------------------------------------------------------


class TransferTicket:
    """Completion handle for one submitted transfer. `wait()` re-raises
    the job's exception on the caller's thread (the r14 async-d2h
    discipline: a failed background copy surfaces at the join, never
    silently)."""

    __slots__ = ("direction", "nbytes", "tag", "result", "error",
                 "_done", "submitted_s", "finished_s")

    def __init__(self, direction: str, nbytes: int, tag: str):
        self.direction = direction
        self.nbytes = int(nbytes)
        self.tag = tag
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self._done = threading.Event()
        self.submitted_s = time.perf_counter()
        self.finished_s = 0.0

    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None):
        ok = self._done.wait(timeout)
        enforce(ok, f"offload transfer {self.direction}/{self.tag} did "
                f"not complete within {timeout}s", exc=TimeoutError)
        if self.error is not None:
            raise self.error
        return self.result


class TransferStream:
    """One FIFO worker thread moving bytes between tiers while the
    compute thread keeps ticking — the shared stream scheduler all
    three offload consumers submit to. Each job runs under an
    `offload` span (kind added to tracing.SPAN_KINDS this round) and
    lands on the exact byte census (`counters()`), which
    tests/test_offload.py holds to the predicted wire bytes.

    The job callable runs ON THE STREAM THREAD: d2h jobs materialize
    jax arrays (`np.asarray` blocks there, overlapping the compute
    thread), h2d jobs stage `jnp.asarray` placements ahead of the
    tick that reads them. Device-side commits (`.at[].set` +
    `scope.set_var`) stay on the compute thread between ticks — jax
    scope mutation is single-writer by design (see
    `PagedKVEngine._pre_tick`)."""

    def __init__(self, name: str = "offload"):
        self.name = name
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._counters = {"d2h_bytes": 0, "h2d_bytes": 0,
                          "d2h_jobs": 0, "h2d_jobs": 0, "busy_s": 0.0}
        self._closed = False
        self._thread = threading.Thread(
            target=self._worker, name=f"ptpu-{name}-stream", daemon=True)
        self._thread.start()

    def submit(self, direction: str, fn: Callable[[], Any],
               nbytes: int, tag: str = "") -> TransferTicket:
        enforce(direction in ("d2h", "h2d"),
                f"transfer direction must be 'd2h' or 'h2d', got "
                f"{direction!r}", exc=InvalidArgumentError)
        enforce(not self._closed, "TransferStream is closed",
                exc=InvalidArgumentError)
        t = TransferTicket(direction, nbytes, tag)
        self._q.put((t, fn))
        return t

    def _worker(self):
        from ..observability import tracing as _tracing
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            ticket, fn = item
            t0 = time.perf_counter()
            try:
                with _tracing.span("offload",
                                   f"offload/{ticket.direction}",
                                   bytes=ticket.nbytes,
                                   tag=ticket.tag):
                    ticket.result = fn()
            except BaseException as e:  # surfaces at ticket.wait()
                ticket.error = e
            t1 = time.perf_counter()
            with self._lock:
                self._counters[f"{ticket.direction}_bytes"] += ticket.nbytes
                self._counters[f"{ticket.direction}_jobs"] += 1
                self._counters["busy_s"] += t1 - t0
            _note_bytes(ticket.direction, ticket.nbytes)
            ticket.finished_s = t1
            ticket._done.set()
            self._q.task_done()

    def drain(self):
        """Block until every submitted job has run (errors stay on
        their tickets)."""
        self._q.join()

    def counters(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._counters)

    def close(self):
        if not self._closed:
            self._closed = True
            self._q.put(None)
            self._thread.join(timeout=5.0)


_shared_stream: Optional[TransferStream] = None
_shared_stream_lock = threading.Lock()


def shared_stream() -> TransferStream:
    """The process-wide transfer stream (one FIFO: KV spill, optimizer
    round-trips and stash traffic serialize here the way one DMA
    engine would)."""
    global _shared_stream
    with _shared_stream_lock:
        if _shared_stream is None or _shared_stream._closed:
            _shared_stream = TransferStream()
        return _shared_stream


# ---------------------------------------------------------------------------
# global offload stats -> ptpu_offload_* gauges
# ---------------------------------------------------------------------------

_stats_lock = threading.Lock()
_stats = {"evictions_total": 0, "prefetch_hits_total": 0,
          "prefetch_misses_total": 0, "d2h_bytes_total": 0,
          "h2d_bytes_total": 0}
_gauges = None


def note_eviction(n_blocks: int = 1):
    with _stats_lock:
        _stats["evictions_total"] += int(n_blocks)


def note_prefetch(hit: bool):
    with _stats_lock:
        _stats["prefetch_hits_total" if hit
               else "prefetch_misses_total"] += 1


def _note_bytes(direction: str, nbytes: int):
    with _stats_lock:
        _stats[f"{direction}_bytes_total"] += int(nbytes)


def offload_stats() -> Dict[str, int]:
    with _stats_lock:
        return dict(_stats)


def offload_metrics():
    """The `ptpu_offload_*` series, registered (idempotently) into
    `metrics.default_registry()` next to `ptpu_memory_*` and
    `ptpu_engine_*` (r16 unified-registry discipline)."""
    global _gauges
    if _gauges is None:
        from ..observability import metrics as m
        r = m.default_registry()
        helps = {
            "evictions_total": "KV blocks evicted device -> host "
                               "(two-tier pager).",
            "prefetch_hits_total": "Suspended-request resumes whose h2d "
                                   "prefetch had already landed.",
            "prefetch_misses_total": "Resumes that had to wait on the "
                                     "h2d transfer (prefetch too late "
                                     "or never issued).",
            "d2h_bytes_total": "Bytes moved device -> host by the "
                               "offload transfer stream.",
            "h2d_bytes_total": "Bytes moved host -> device by the "
                               "offload transfer stream.",
        }
        _gauges = {
            k: m.get_or_create(r, "gauge", f"ptpu_offload_{k}", h,
                               fn=(lambda k=k: _stats[k]))
            for k, h in helps.items()}
    return _gauges


def reset_offload():
    """Test isolation: zero the stats and replace the shared pool (the
    shared stream survives — it is stateless beyond its counters)."""
    global _shared_pool
    with _stats_lock:
        for k in _stats:
            _stats[k] = 0
    with _shared_pool_lock:
        _shared_pool = PinnedHostPool()


# ---------------------------------------------------------------------------
# host-resident optimizer state (ZeRO-offload, consumer b)
# ---------------------------------------------------------------------------


def optimizer_state_names(program, scope) -> List[str]:
    """The scope vars that are optimizer state per the ONE classifier
    (`costs.state_category` — the same walk the census and the ledger
    use, so the offloaded set cannot drift from the priced set)."""
    from . import costs as _costs
    names: List[str] = []
    seen = set()
    for b in program.blocks:
        for name, v in b.vars.items():
            if name in seen or not scope.has_var(name):
                continue
            seen.add(name)
            if _costs.state_category(v, name) == "optimizer_state":
                names.append(name)
    return sorted(names)


class HostOptimizerState:
    """ZeRO-offload one tier further: between steps the ZeRO-1
    accumulator shards live ONLY in the pinned host pool; `restore()`
    materializes them back into the scope before the next dispatch and
    `offload()` drops the device copies after the step, with the d2h
    running on the transfer stream behind whatever the host does next
    (next-batch prep, dispatch assembly).

    The round-trip is bitwise (numpy staging preserves exact bytes),
    so offload-on training is loss-identical to offload-off — asserted
    by tests/test_offload.py.


    CPU-mesh caveat: jit consumes every argument at dispatch, so the
    full shard is device-resident DURING the step; the streamed
    per-bucket round-trip the `costs.predict` offload section prices
    (resident working set = one comm bucket) needs the TPU runtime's
    per-bucket donation. Between steps the device census genuinely
    shows optimizer_state == 0 — that part is measurable here."""

    def __init__(self, scope, names: Sequence[str],
                 stream: Optional[TransferStream] = None,
                 pool: Optional[PinnedHostPool] = None):
        enforce(len(names) > 0,
                "HostOptimizerState: no optimizer-state vars to offload "
                "(run the built train step once so the accumulators "
                "exist, or drop offload_optimizer_state)",
                exc=InvalidArgumentError)
        self.scope = scope
        self.names = list(names)
        self.stream = stream or shared_stream()
        self.pool = pool or shared_host_pool()
        self._bufs: Dict[str, HostBuffer] = {}
        self._tickets: Dict[str, TransferTicket] = {}
        self.offloaded = False
        self.roundtrips = 0
        self.last_restore_wait_s = 0.0
        self.bytes_per_direction = 0

    def offload(self):
        """Async d2h: snapshot every accumulator into its pool buffer
        on the stream thread, then erase the device copies from the
        scope (the next `restore()` is what puts them back — the
        ParallelExecutor.run wiring guarantees the order)."""
        if self.offloaded:
            return
        total = 0
        for name in self.names:
            arr = self.scope.get(name)
            nb = int(getattr(arr, "nbytes", 0))
            buf = self._bufs.get(name)
            if buf is None or buf.array.nbytes != nb \
                    or buf.array.dtype != arr.dtype:
                if buf is not None:
                    self.pool.free(buf)
                buf = self.pool.alloc(arr.shape, arr.dtype, "optimizer")
                self._bufs[name] = buf

            def _copy(arr=arr, buf=buf):
                # np.asarray blocks on the step's async result HERE,
                # on the stream thread — the overlap the census times
                np.copyto(buf.array, np.asarray(arr))

            self._tickets[name] = self.stream.submit(
                "d2h", _copy, buf.nbytes, tag=name)
            total += buf.nbytes
            self.scope.erase(name)
        self.bytes_per_direction = total
        self.offloaded = True

    def restore(self):
        """h2d: wait the in-flight d2h (usually long done — the wait
        time is the measured non-overlap) and place each shard back on
        device. Bytes move on the stream so the census counts them."""
        if not self.offloaded:
            return
        import jax.numpy as jnp
        t0 = time.perf_counter()
        for name in self.names:
            t = self._tickets.pop(name, None)
            if t is not None:
                t.wait(timeout=60.0)
        self.last_restore_wait_s = time.perf_counter() - t0
        for name in self.names:
            buf = self._bufs[name]
            ticket = self.stream.submit(
                "h2d", (lambda b=buf: jnp.asarray(b.array)),
                buf.nbytes, tag=name)
            self.scope.set_var(name, ticket.wait(timeout=60.0))
        self.offloaded = False
        self.roundtrips += 1

    def release(self):
        """Return the scratch buffers to the pool (state must be
        device-resident — call `restore()` first)."""
        enforce(not self.offloaded,
                "HostOptimizerState.release while state is host-resident"
                " — restore() first", exc=InvalidArgumentError)
        for buf in self._bufs.values():
            self.pool.free(buf)
        self._bufs.clear()


# ---------------------------------------------------------------------------
# transfer schedules — the lintable policy surface
# ---------------------------------------------------------------------------


@dataclass
class TransferEvent:
    """One planned tier move, in tick (serving) or op-index (training)
    time: issued at `issue_tick`, data resident by `arrive_tick`, first
    consumed at `read_tick`. The invariant `lint_program --offload`
    enforces: arrival strictly before-or-at the read."""
    var: str
    direction: str            # "d2h" | "h2d"
    issue_tick: int
    arrive_tick: int
    read_tick: int


def prefetch_issue_tick(read_tick: int, prefetch_distance: int) -> int:
    """When to start the h2d prefetch of blocks scheduled to be read at
    `read_tick` — the ONE policy helper the two-tier engine executes
    and `lint_program --offload` checks (shared code, not a copy, so
    the linted schedule is the shipped schedule)."""
    return int(read_tick) - int(prefetch_distance)


def kv_prefetch_events(read_ticks: Dict[str, int],
                       prefetch_distance: int) -> List[TransferEvent]:
    """The two-tier KV prefetch schedule for suspended requests whose
    projected resume ticks are `read_ticks` ({request -> tick})."""
    out = []
    for var, read in sorted(read_ticks.items()):
        issue = prefetch_issue_tick(read, prefetch_distance)
        out.append(TransferEvent(var=var, direction="h2d",
                                 issue_tick=issue, arrive_tick=read,
                                 read_tick=read))
    return out


def optimizer_roundtrip_events(program, *, restore_at: int = 0
                               ) -> List[TransferEvent]:
    """The host-resident optimizer round-trip as op-index events over
    one train step: every accumulator must be back on device at
    `restore_at` (step entry — jit consumes all arguments at dispatch)
    and spills after its LAST access. A restore point after an op that
    reads the var is exactly `offload-use-before-arrival`."""
    from . import costs as _costs
    events: List[TransferEvent] = []
    block = program.blocks[0]
    acc = {name for name, v in block.vars.items()
           if _costs.state_category(v, name) == "optimizer_state"}
    if not acc:
        return events
    first_read: Dict[str, int] = {}
    last_access: Dict[str, int] = {}
    for idx, op in enumerate(block.ops):
        names = set()
        for ns in getattr(op, "inputs", {}).values():
            names.update(ns)
        read = {n for n in names if n in acc}
        for ns in getattr(op, "outputs", {}).values():
            names.update(ns)
        for n in names:
            if n in acc:
                last_access[n] = idx
        for n in read:
            first_read.setdefault(n, idx)
    n_ops = len(block.ops)
    for name in sorted(acc):
        events.append(TransferEvent(
            var=name, direction="h2d", issue_tick=restore_at,
            arrive_tick=restore_at,
            read_tick=first_read.get(name, n_ops)))
        events.append(TransferEvent(
            var=name, direction="d2h",
            issue_tick=last_access.get(name, n_ops),
            arrive_tick=n_ops, read_tick=n_ops))
    return events


def check_schedule(events: Sequence[TransferEvent],
                   rollback_windows: Optional[Dict[str, Sequence[int]]]
                   = None) -> List[Any]:
    """r13 named-diagnostic discipline: a transfer that arrives (or is
    even issued) after its first read is the error-severity
    `offload-use-before-arrival` diagnostic. Returns
    `analysis.Diagnostic` rows for `lint_program --offload`.

    r24: `rollback_windows` ({var -> rollback ticks}) extends the check
    to speculative serving. A rollback at tick t rewrites the var's
    device blocks; any in-flight transfer issued BEFORE t but consumed
    AT-OR-AFTER t carries the pre-rollback bytes — the reader would see
    tokens the verifier already rejected. That is
    `offload-stale-after-rollback`: the transfer must be re-issued
    after the rollback it straddles."""
    from .analysis import Diagnostic
    out = []
    for ev in events:
        if ev.arrive_tick > ev.read_tick or ev.issue_tick > ev.read_tick:
            out.append(Diagnostic(
                code="offload-use-before-arrival",
                loc=ev.var,
                message=(f"{ev.direction} scheduled at tick "
                         f"{ev.issue_tick} (arrives {ev.arrive_tick}) "
                         f"but first read is tick {ev.read_tick} — the "
                         f"consumer would see the stale tier"),
                severity="error"))
    for ev in events:
        for t in (rollback_windows or {}).get(ev.var, ()):
            if ev.issue_tick < t <= ev.read_tick:
                out.append(Diagnostic(
                    code="offload-stale-after-rollback",
                    loc=ev.var,
                    message=(f"{ev.direction} issued at tick "
                             f"{ev.issue_tick} straddles the rollback "
                             f"at tick {t} (read at {ev.read_tick}) — "
                             f"the transfer carries rejected "
                             f"speculative bytes and must be re-issued "
                             f"after the rollback"),
                    severity="error"))
    return out
