"""Structured step tracing: typed, nested spans in a lock-cheap ring.

The r03-r11 probes each re-invented span timing with ad-hoc
`time.perf_counter()` pairs; the profiler shim recorded flat host events
only while a profiler context was open. This module is the ONE recorder:

- `span(kind, name, **attrs)` — a context manager recording a typed,
  NESTED interval (parent/depth come from a per-thread stack) with
  provenance attributes (op_loc strings, pass names, schedule configs);
- recording appends into a preallocated ring buffer; the only shared
  mutation on the hot path is one `itertools.count()` draw (atomic under
  the GIL) plus a slot store, so concurrent threads never contend on a
  lock;
- kill switch `PTPU_TRACE=0` (core flag `trace`) makes `__enter__`/
  `__exit__` near-free — the overhead budget for BOTH states is asserted
  in tests/test_observability.py;
- `export_chrome_trace()` / `aggregate()` turn the ring into the Chrome
  (catapult) timeline and the per-span summary tables;
  `paddle_tpu/profiler.py` keeps its fluid-compatible surface as a thin
  window over this ring (`RecordEvent` == a "user" span);
- `compile_span(name, program)` is the span around a jitted function's FIRST
  call: JAX's own seconds of tracing, lowering, compiling and loading from
  the persistent cache (one `jax.monitoring` listener, registered here) land
  on the innermost one open on the compiling thread, and otherwise in the
  open `jax/unscoped` record, which `mark()` closes into a span. Spans of
  kind `compile` are rare and told once, so they are ALSO kept beside the
  ring (`compile_spans()`): a ring that wrapped or was resized still says
  what a process compiled, and when.

Span kinds are CLOSED (SPAN_KINDS): a typo'd kind raises instead of
minting a new category that no aggregation ever finds.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax.monitoring

from ..core import flags
from ..core.enforce import (InvalidArgumentError, OutOfRangeError,
                            enforce)

SPAN_KINDS = frozenset({
    "compile",     # what a process pays once: the package's import, a step
                   # function built, a jitted function's first call (its
                   # trace, lowering, XLA compile or cache load); kept
                   # beside the ring too (compile_spans)
    "step",        # one executor.run / run_steps dispatch
    "tick",        # one serving-engine decode tick
    "collective",  # host-side collective setup (placement, reconcile)
    "feed_fetch",  # feed placement / fetch realization & write-back
    "admission",   # serving-engine request admission
    "pp_tick",     # pipeline schedule construction / tick tables
    "dp_comm",     # explicit gradient-comm rewrite planning
    "pass",        # any registered Pass application (provenance = name)
    "checkpoint",  # elastic snapshot/restore phases (parallel/elastic.py)
    "request",     # one serving request's lifecycle phases (queue_wait/
                   # prefill/decode/transport, serving_engine.py)
    "memory",      # memory watermark sample (record_counter; rendered as
                   # a Chrome COUNTER track, observability/memory.py)
    "dispatch",    # host-side argument assembly + write-back around the
                   # compiled tick fn (serving engine zero-dispatch path)
    "speculate",   # one speculative round's draft-model propose phase
                   # (γ+1 bound draft ticks, serving/speculative.py)
    "verify",      # the round's single target verify forward over the
                   # γ+1-wide window (serving/speculative.py)
    "offload",     # one host-tier transfer job on the offload stream
                   # (d2h spill / h2d prefetch, framework/offload.py)
    "user",        # RecordEvent-style user annotation
})


class Span:
    """One completed interval. Slots only — the ring holds up to
    `trace_ring` of these."""

    __slots__ = ("kind", "name", "start", "end", "thread_id", "parent",
                 "depth", "attrs", "seq", "id", "parent_id")

    def __init__(self, kind, name, start, end, thread_id, parent, depth,
                 attrs, seq, id=-1, parent_id=-1):
        self.kind = kind
        self.name = name
        self.start = start
        self.end = end
        self.thread_id = thread_id
        self.parent = parent       # enclosing span's name ('' at top level)
        self.depth = depth
        self.attrs = attrs
        self.seq = seq
        self.id = id               # drawn at ENTER (seq is drawn at exit)
        self.parent_id = parent_id  # enclosing live span's id, -1 at top

    @property
    def duration_ms(self) -> float:
        return (self.end - self.start) * 1e3

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "name": self.name,
                "duration_ms": round(self.duration_ms, 6),
                "parent": self.parent, "depth": self.depth,
                "id": self.id, "parent_id": self.parent_id,
                "thread_id": self.thread_id, "attrs": self.attrs}


# ring storage: preallocated slot list + monotone counter. next(_seq) is
# atomic under the GIL; each writer owns its slot exclusively, so no lock
# is taken on the record path.
_ring: List[Optional[Span]] = []
_ring_cap = 0
_seq = itertools.count()
_ids = itertools.count()    # span ids, drawn at enter; retroactive spans
#                             and counter samples draw one when recorded
_resize_lock = threading.Lock()

# per-thread nesting stack: the thread's live `span` scopes, outermost
# first — plus the thread's tag dict (scoped_tags), merged into every span
# the thread records
_tls = threading.local()


class scoped_tags:
    """Tag every span recorded by THIS thread while the scope is open:

        with tracing.scoped_tags(world="w1", rank=2, world_size=4):
            ...   # every span (and record_span) carries these attrs

    Scopes nest (inner tags shadow outer ones of the same key, the rest
    merge); a span's own attrs win over thread tags. This is how the
    process-world rank threads stamp {world_id, rank, world_size} onto
    every span they record without threading the identity through every
    instrumented callsite."""

    __slots__ = ("tags", "_prev")

    def __init__(self, **tags):
        self.tags = tags

    def __enter__(self):
        self._prev = getattr(_tls, "tags", None)
        merged = dict(self._prev) if self._prev else {}
        merged.update(self.tags)
        _tls.tags = merged
        return self

    def __exit__(self, *exc):
        _tls.tags = self._prev
        return False


def rank_scope(world: str, rank: int, world_size: int) -> scoped_tags:
    """The distributed-tracing tag triple: every span this thread records
    is attributed to (world, rank) — tools/trace_merge.py turns the rank
    into a Chrome-trace pid lane."""
    return scoped_tags(world=str(world), rank=int(rank),
                       world_size=int(world_size))


def current_tags() -> Dict[str, Any]:
    """This thread's active scoped_tags (empty dict outside any scope)."""
    tags = getattr(_tls, "tags", None)
    return dict(tags) if tags else {}

# profiler interop: incremented while the legacy profiler context is
# active (spans then record even with the trace flag down — the old
# RecordEvent contract), and an optional device-annotation factory set
# while a jax.profiler device trace runs.
_force_count = 0
annotation_factory: Optional[Callable[[str], Any]] = None


def _ensure_ring():
    global _ring, _ring_cap, _ring_raw
    raw = _RING_FLAG.value
    if raw is _ring_raw:      # unchanged since it was last validated
        return _ring
    try:
        cap = int(raw)
    except (TypeError, ValueError):
        raise InvalidArgumentError(
            f"PTPU_TRACE_RING (flag trace_ring) must be a positive "
            f"integer span-ring capacity, got {raw!r}") from None
    if cap < 1:
        raise InvalidArgumentError(
            f"PTPU_TRACE_RING (flag trace_ring) must be >= 1 (the span "
            f"ring needs at least one slot), got {cap}")
    with _resize_lock:
        if cap != _ring_cap:
            _ring = [None] * cap
            _ring_cap = cap
        _ring_raw = raw
    return _ring


# the flag SPEC object is stable across set_flag calls (set_flag mutates
# .value in place) — holding it dodges a registry lookup per span on the
# hot path
_TRACE_FLAG = flags._REGISTRY["trace"]
_RING_FLAG = flags._REGISTRY["trace_ring"]
_ring_raw = object()    # the trace_ring value the ring was last sized for


def enabled() -> bool:
    return bool(_TRACE_FLAG.value) or _force_count > 0


def force_enable(on: bool):
    """Used by paddle_tpu.profiler: while a profiler() context is open,
    spans record regardless of the PTPU_TRACE flag (the pre-r12
    RecordEvent contract)."""
    global _force_count
    _force_count += (1 if on else -1)
    if _force_count < 0:
        _force_count = 0


def mark() -> int:
    """Current ring position — pass to spans_since() to read only spans
    recorded after this point (the profiler window / bench breakdowns)."""
    _ensure_ring()
    # peek without consuming: count() has no peek, so mint-and-remember
    # would skip a slot. Track via a sacrificial draw is wrong; instead
    # the mark is the NEXT sequence number, derived from a draw we then
    # hand to no span — acceptable: one empty slot per mark.
    if _unscoped is not None:
        _close_unscoped()
    return next(_seq)


def _record(span: Span):
    # index with the CAPTURED ring's own length: a concurrent trace_ring
    # resize swaps _ring/_ring_cap as a pair, and mixing the old list
    # with the new cap would IndexError out of span.__exit__ on an
    # instrumented hot path
    ring = _ensure_ring()
    ring[span.seq % len(ring)] = span
    if span.kind == "compile":
        _keep(span)


class span:
    """RAII span scope. Usage:

        with span("pass", "tp_shard_pass", tp=2) as sp:
            ...
            sp.attrs["moved"] = n      # counts taken where the work happens

    Attributes must be JSON-serializable scalars/strings (op_loc output,
    config ints) — they land in the Chrome trace `args` and the ledger;
    they are read at EXIT, so a scope may add what it counted. A live span
    draws its `id` at enter and knows its parent's: `self_time_ms` rebuilds
    the tree from the pairs. When disabled, enter/exit touch one module
    global and return.
    """

    __slots__ = ("kind", "name", "attrs", "id", "_start", "_parent",
                 "_stack", "_annotation")

    def __init__(self, kind: str, name: Optional[str] = None, **attrs):
        if kind not in SPAN_KINDS:   # no eager f-string on the hot path
            raise InvalidArgumentError(
                f"unknown span kind {kind!r}; known: "
                f"{sorted(SPAN_KINDS)}")
        self.kind = kind
        self.name = name or kind
        self.attrs = attrs
        self._stack = None           # the thread's stack while live

    def __enter__(self):
        if not (_TRACE_FLAG.value or _force_count):
            return self
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _tls.stack = []
        self._parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self)
        self._stack = stack
        self._annotation = None
        if annotation_factory is not None:
            try:
                self._annotation = annotation_factory(self.name)
                self._annotation.__enter__()
            except Exception:
                self._annotation = None
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        stack = self._stack
        if stack is None:
            return False
        end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        depth = len(stack) - 1
        if stack[-1] is self:
            stack.pop()
        else:                        # scopes closed out of order
            depth = stack.index(self)
            stack.remove(self)
        self._stack = None
        parent = self._parent
        self._parent = None
        tags = getattr(_tls, "tags", None)
        if tags:
            self.attrs = {**tags, **self.attrs}
        # the record holds the scope's own dict: a count that comes in after
        # the exit (a tick's ids read a launch late) still lands on it
        _record(Span(self.kind, self.name, self._start, end,
                     threading.get_ident(),
                     parent.name if parent is not None else "", depth,
                     self.attrs, next(_seq), self.id,
                     parent.id if parent is not None else -1))
        return False


def record_span(kind: str, name: str, start: float, end: float,
                **attrs) -> Optional[Span]:
    """Record a RETROACTIVE span from externally measured perf_counter
    timestamps — phases whose boundaries were observed as plain floats
    (a request's queue-wait between submit and slot assignment, a
    barrier phase reconstructed from beacon notes) become first-class
    spans on the same timeline the live `span` scopes draw on. Thread
    tags (scoped_tags) merge in exactly like live spans; returns None
    when tracing is disabled."""
    if kind not in SPAN_KINDS:
        raise InvalidArgumentError(
            f"unknown span kind {kind!r}; known: {sorted(SPAN_KINDS)}")
    if not (_TRACE_FLAG.value or _force_count):
        return None
    tags = getattr(_tls, "tags", None)
    if tags:
        attrs = {**tags, **attrs}
    s = Span(kind, name, float(start), float(end),
             threading.get_ident(), "", 0, attrs, next(_seq), next(_ids))
    _record(s)
    return s


def record_counter(name: str, value: float, **attrs) -> Optional[Span]:
    """Record one SAMPLE on the `memory` channel: a zero-duration span
    whose `value` attr is the sampled level (a watermark's current bytes,
    an MFU reading). Samples ride the same ring as interval spans — one
    counter draw, no lock — and `chrome_trace_events` renders them as
    Chrome COUNTER events (`ph: "C"`), i.e. a plotted track per sample
    name, so memory levels read as a line under the span lanes. Thread
    tags (scoped_tags / rank_scope) merge in exactly like live spans;
    returns None when tracing is disabled."""
    if not (_TRACE_FLAG.value or _force_count):
        return None
    now = time.perf_counter()
    tags = getattr(_tls, "tags", None)
    attrs = ({**tags, "value": float(value), **attrs} if tags
             else {"value": float(value), **attrs})
    s = Span("memory", name, now, now, threading.get_ident(), "", 0,
             attrs, next(_seq), next(_ids))
    _record(s)
    return s


# -- compiles ----------------------------------------------------------------
# jax.jit is lazy: a function's trace, its lowering and the XLA compile (or
# the load from the persistent cache) all happen inside its FIRST call. JAX
# times each itself and tells whoever listens; `compile_span` is where those
# seconds get a place and a program's name.

#: the duration events JAX reports, by what this module files them under
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "load",
}
_KEPT_CAP = 4096
_kept: "collections.deque[Span]" = collections.deque(maxlen=_KEPT_CAP)
_kept_dropped = 0
_unscoped: Optional["_JaxSeconds"] = None    # the open jax/unscoped record
_unscoped_lock = threading.Lock()


class _JaxSeconds:
    """JAX's own seconds of one first call (or of everything outside one),
    summed by kind as the listener hears them."""

    __slots__ = ("first", "last", "traces", "trace", "longest", "lower",
                 "backend", "executables", "load", "loads")

    def __init__(self):
        self.first = self.last = None    # where the events lie, perf_counter
        self.traces = self.executables = self.loads = 0
        self.trace = self.longest = self.lower = 0.0
        self.backend = self.load = 0.0

    def add(self, what, seconds):
        now = time.perf_counter()
        if self.first is None:
            self.first = now - seconds
        self.last = now
        if what == "trace":
            self.traces += 1
            self.trace += seconds
            self.longest = max(self.longest, seconds)
        elif what == "lower":
            self.lower += seconds
        elif what == "backend":
            self.executables += 1
            self.backend += seconds
        else:
            self.loads += 1
            self.load += seconds

    def attrs(self, nested):
        """The span's attrs. Traces NEST inside a first call (a jitted
        helper's trace is an event of its own inside the step's): the longest
        is the outermost, the others an upper bound of what nested in it.
        Outside one every trace is counted whole. JAX's backend event holds
        the cache's retrieval when there is one: `compile_s` is it LESS the
        retrieval, ~0 where every executable came from the cache;
        `cache_loads` counts the retrievals, `executables` less it the
        executables XLA compiled."""
        outer = self.longest if nested else self.trace
        inner = max(self.traces - 1, 0) if nested else self.traces
        return {"trace_s": outer, "nested_trace_s": self.trace - outer,
                "jits": inner, "lower_s": self.lower,
                "compile_s": max(self.backend - self.load, 0.0),
                "cache_load_s": self.load, "executables": self.executables,
                "cache_loads": self.loads,
                "cache_hit": int(0 < self.executables <= self.loads)}


class compile_span(span):
    """The live span of kind `compile` around the first call of a jitted
    function: `with compile_span("executor/compile_or_load", "train_step")`.
    While it is open JAX's duration events on THIS thread add into it (the
    innermost one, where they nest), and at exit it carries `program`,
    `trace_s`, `nested_trace_s`, `jits`, `lower_s`, `compile_s`,
    `cache_load_s`, `executables`, `cache_loads`, `cache_hit`
    (`_JaxSeconds.attrs`) and
    `kernel_calls` / `kernel_bodies_traced`: the `*/call` and `*/body_traced`
    counter samples this thread recorded meanwhile, read in the ring. A
    first call that found its executable in the executor's store traced and
    lowered nothing (`framework/executor.py` `_FirstRun`): it tells the span
    the load's seconds as JAX tells a retrieval from its own cache, and
    every count that comes of a trace reads 0."""

    __slots__ = ("_jax", "_seq0")

    def __init__(self, name: str, program: str):
        super().__init__("compile", name, program=program)

    def __enter__(self):
        super().__enter__()
        if self._stack is not None:
            self._jax = _JaxSeconds()
            self._seq0 = next(_seq)      # a mark of its own: one empty slot
        return self

    def __exit__(self, *exc):
        if self._stack is not None:
            calls, bodies = _kernel_samples(self._seq0, self._start)
            self.attrs.update(self._jax.attrs(nested=True),
                              kernel_calls=calls, kernel_bodies_traced=bodies)
        return super().__exit__(*exc)


def _kernel_samples(seq0, start):
    """(calls, bodies traced): the kernels' set-up counter samples this
    thread recorded after slot `seq0`, found in the ring where they are."""
    ring, me = _ring, threading.get_ident()
    n, upto = len(ring), next(_seq)
    found = (ring[i % n] for i in range(seq0 + 1, upto)) \
        if upto - seq0 <= n else iter(ring)
    calls = bodies = 0
    for s in found:
        if (s is not None and s.kind == "memory" and s.thread_id == me
                and s.start >= start):
            calls += s.name.endswith("/call")
            bodies += s.name.endswith("/body_traced")
    return calls, bodies


def _on_jax_duration(event, seconds, **_):
    """THE `jax.monitoring` duration listener of the tree."""
    global _unscoped
    if not (_TRACE_FLAG.value or _force_count):
        return
    what = _JAX_EVENTS.get(event)
    if what is None:
        return
    for s in reversed(getattr(_tls, "stack", ())):
        if isinstance(s, compile_span):
            s._jax.add(what, seconds)
            return
    with _unscoped_lock:
        if _unscoped is None:
            _unscoped = _JaxSeconds()
        _unscoped.add(what, seconds)


def _unscoped_span(rec):
    """The `jax/unscoped` record as a span: what JAX traced, lowered,
    compiled or loaded with NO compile_span open on the thread (a weight
    builder's jitted generator, an eager `jnp` op). SUMS over whatever
    threads compiled between its first event and its last, and no interval
    the process spent compiling: nobody stood around these events."""
    return Span("compile", "jax/unscoped", rec.first, rec.last,
                threading.get_ident(), "", 0,
                dict(rec.attrs(nested=False), program="unscoped"), -1)


def _close_unscoped():
    global _unscoped
    with _unscoped_lock:
        rec, _unscoped = _unscoped, None
    if rec is not None:
        s = _unscoped_span(rec)
        s.seq, s.id = next(_seq), next(_ids)
        _record(s)


def _keep(s: Span):
    global _kept_dropped
    if len(_kept) == _KEPT_CAP:
        _kept_dropped += 1
    _kept.append(s)


def compile_spans() -> List[Span]:
    """Every span of kind `compile` this process recorded, oldest first, the
    newest 4,096 of them (`compile_spans_dropped()` counts the rest): kept
    BESIDE the ring, so they outlive its wrapping and a `trace_ring` resize.
    `clear()` empties the list; with tracing off nothing is kept. The open
    `jax/unscoped` record, where it holds anything, comes last, as it
    stands."""
    out = list(_kept)
    rec = _unscoped
    if rec is not None:
        out.append(_unscoped_span(rec))
    return out


def compile_spans_dropped() -> int:
    return _kept_dropped


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def clear():
    """Drop every recorded span, the kept `compile` spans and the open
    `jax/unscoped` record with them (test isolation; profiler.reset)."""
    global _ring, _seq, _unscoped, _kept_dropped
    with _resize_lock:
        _ring = [None] * max(_ring_cap, 1)
        _seq = itertools.count()
    with _unscoped_lock:
        _unscoped = None
        _kept.clear()
        _kept_dropped = 0


def spans(since: Optional[int] = None) -> List[Span]:
    """All live spans in record order; `since` (a mark()) filters to spans
    recorded after that point."""
    out = [s for s in _ring if s is not None]
    out.sort(key=lambda s: s.seq)
    if since is not None:
        out = [s for s in out if s.seq >= since]
    return out


def spans_since(mark_value: int) -> List[Span]:
    """The WHOLE window since a mark(), or an error: when the ring has
    wrapped past the mark the window's head is overwritten, and a median
    over what is left reads like one over the window. `spans(since=...)`
    is the lenient read (the profiler's report, which says so itself)."""
    out = spans()
    lost = out[-1].seq - len(_ring) - mark_value if out else 0
    if lost > 0:
        raise OutOfRangeError(
            f"the span ring (PTPU_TRACE_RING={len(_ring)}) wrapped: the "
            f"oldest {lost} records since mark {mark_value} are "
            f"overwritten; raise PTPU_TRACE_RING or read shorter windows")
    return [s for s in out if s.seq >= mark_value]


def _self_ms(span_list: List[Span]) -> List[float]:
    """For each span of the list, in its order: its duration minus the
    union of its DIRECT children's intervals, in ms. Children are found by
    `parent_id` and clipped to the parent; retroactive spans and counter
    samples have no parent and are nobody's child."""
    kids: Dict[int, list] = {}
    for s in span_list:
        if s.parent_id >= 0:
            kids.setdefault(s.parent_id, []).append((s.start, s.end))
    out = []
    for s in span_list:
        covered, reach = 0.0, s.start
        for a, b in sorted(kids.get(s.id, ())):
            a, b = max(a, reach), min(b, s.end)
            if b > a:
                covered += b - a
                reach = b
        out.append((s.end - s.start - covered) * 1e3)
    return out


def self_time_ms(span_list: List[Span], name: str) -> List[float]:
    """For each span called `name`, in the list's order: the time it spent
    under NO direct child — what a scope did itself, or what nobody named.
    The one place the subtraction is written; metric readers call it."""
    return [own for s, own in zip(span_list, _self_ms(span_list))
            if s.name == name]


def aggregate(span_list: Optional[List[Span]] = None,
              by: str = "name") -> Dict[str, Dict]:
    """Per-span summary table: {key: {calls, total_ms, self_ms, max_ms,
    min_ms, avg_ms, kind}} — the profiler report and the benchmark span_ms
    rows both read this. `by` is 'name' or 'kind'; `self_ms` is the total
    of `self_time_ms` over the row's spans."""
    enforce(by in ("name", "kind"), f"aggregate by {by!r}?",
            exc=InvalidArgumentError)
    span_list = spans() if span_list is None else span_list
    rows: Dict[str, Dict] = {}
    for s, own in zip(span_list, _self_ms(span_list)):
        key = s.name if by == "name" else s.kind
        r = rows.get(key)
        d = s.duration_ms
        if r is None:
            rows[key] = {"kind": s.kind, "calls": 1, "total_ms": d,
                         "self_ms": own, "max_ms": d, "min_ms": d}
        else:
            r["calls"] += 1
            r["total_ms"] += d
            r["self_ms"] += own
            r["max_ms"] = max(r["max_ms"], d)
            r["min_ms"] = min(r["min_ms"], d)
    for r in rows.values():
        r["avg_ms"] = r["total_ms"] / r["calls"]
    return rows


def chrome_trace_events(span_list: Optional[List[Span]] = None,
                        pid: int = 0) -> List[Dict]:
    """Spans as Chrome (catapult) complete events; nesting renders from
    the overlapping ts/dur intervals per thread lane."""
    evs = []
    for s in (spans() if span_list is None else span_list):
        if s.kind == "memory":
            # counter sample -> Chrome COUNTER event: args values are
            # plotted as a track named after the sample. Non-numeric
            # attrs (rank tags) ride along for trace_merge's lane
            # assignment and are ignored by the counter renderer.
            evs.append({
                "name": s.name, "cat": s.kind, "ph": "C",
                "ts": s.start * 1e6, "pid": pid, "tid": s.thread_id,
                "args": dict(s.attrs),
            })
            continue
        evs.append({
            "name": s.name, "cat": s.kind, "ph": "X",
            "ts": s.start * 1e6, "dur": (s.end - s.start) * 1e6,
            "pid": pid, "tid": s.thread_id,
            "args": {**s.attrs, "parent": s.parent, "depth": s.depth,
                     "id": s.id, "parent_id": s.parent_id},
        })
    return evs


def export_chrome_trace(path: str,
                        span_list: Optional[List[Span]] = None) -> str:
    """Write the ring (or a filtered list) as ONE Chrome trace JSON."""
    trace = {"traceEvents": chrome_trace_events(span_list),
             "displayTimeUnit": "ms"}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def span_overhead_s(n: int = 2000) -> float:
    """Measured per-span enter/exit cost IN THE CURRENT enabled state —
    the number the overhead-budget assertions multiply by spans-per-step.
    Best of 3 windows so a scheduler blip doesn't fail the budget."""
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with span("user", "overhead_probe"):
                pass
        dt = (time.perf_counter() - t0) / n
        best = dt if best is None else min(best, dt)
    return best
