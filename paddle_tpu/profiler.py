"""Profiler: fluid-compatible surface over the observability tracer.

Capability equivalent of the reference profiler stack (reference:
paddle/fluid/platform/profiler.h:73-121 RecordEvent/EnableProfiler,
platform/device_tracer.h:49 CUPTI tracer, tools/timeline.py Chrome-trace
export, python/paddle/fluid/profiler.py context managers).

Since r12 the actual recorder is `paddle_tpu.observability.tracing`: one
ring buffer of typed nested spans shared by the executors, the rewrite
passes, and the serving engine. This module keeps the fluid-shaped API
as a thin WINDOW over that ring — `start_profiler` marks a position,
`stop_profiler` aggregates/export everything recorded since — so the
pre-r12 contract (RecordEvent records while a profiler context is open,
even with PTPU_TRACE=0) still holds, and the global-state leakage the
old module suffered (events and the enabled bit bleeding across test
suites) is gone: `reset()` restores every module global, and the test
conftest calls it around each test.

Device-side (XPlane) tracing is unchanged: state 'All' starts a
jax.profiler trace when a trace dir is configured, RecordEvent names
ride onto the device timeline as TraceAnnotations, and export merges
host + device events into one Chrome trace.
"""

from __future__ import annotations

import json
import os
from contextlib import contextmanager
from typing import Optional

from .core import flags
from .core.enforce import InvalidArgumentError, enforce
from .observability import tracing as _tracing

_enabled = False
_forced = False           # we hold one tracing.force_enable(True) ref
_trace_dir: Optional[str] = None
_device_tracing = False
_window_mark = 0          # ring position where the current window began


class RecordEvent(_tracing.span):
    """RAII scope annotation (≙ platform::RecordEvent, profiler.h:73) —
    a thin alias over the observability span API (kind 'user'). Nesting
    shows up in the Chrome trace via overlapping ts/dur spans and in the
    span's parent/depth attribution.

    While a device (XPlane) trace is active, the same name is additionally
    entered as a jax.profiler.TraceAnnotation, so it appears ON the device
    timeline correlated with the XLA ops dispatched inside the scope — the
    RecordEvent→device correlation the reference gets from CUPTI
    correlation ids (device_tracer.h:49 + tools/timeline.py:45)."""

    def __init__(self, name: str):
        super().__init__("user", name)


record_event = RecordEvent  # snake_case alias used by layers/executor


def reset_profiler():
    """≙ fluid.profiler.reset_profiler — drop all recorded events (the
    summary/export window restarts here; the tracer ring itself keeps
    spans for observability consumers)."""
    global _window_mark
    _window_mark = _tracing.mark()


def reset():
    """Full state reset for test isolation: disable recording, release
    the force-enable ref, detach the device-annotation factory, and
    restart the window. Safe to call at any point, any number of times
    (tests/conftest.py runs it around every test so neither recorded
    events nor the enabled bit bleed between suites)."""
    global _enabled, _forced, _device_tracing, _trace_dir
    if _forced:
        _tracing.force_enable(False)
        _forced = False
    _enabled = False
    _device_tracing = False
    _trace_dir = None
    _tracing.annotation_factory = None
    reset_profiler()


def start_profiler(state: str = "All", tracer_option: Optional[str] = None):
    """Enable host-event recording; state 'All' additionally starts a
    jax.profiler device trace when a trace dir was configured via
    `profiler(..., output=dir)` or PTPU_TRACE_DIR env.

    ≙ EnableProfiler (reference profiler.h:116; states CPU/GPU/All map to
    host-only vs host+device here).
    """
    global _enabled, _forced, _trace_dir, _device_tracing, _window_mark
    enforce(state in ("CPU", "GPU", "All", "TPU"),
            f"invalid profiler state {state!r}", exc=InvalidArgumentError)
    if not _enabled:
        _window_mark = _tracing.mark()
    _enabled = True
    if not _forced:
        _tracing.force_enable(True)
        _forced = True
    if state in ("GPU", "All", "TPU"):
        trace_dir = _trace_dir or os.environ.get("PTPU_TRACE_DIR")
        if trace_dir:
            import jax
            try:
                jax.profiler.start_trace(trace_dir)
                _device_tracing = True
                _tracing.annotation_factory = jax.profiler.TraceAnnotation
            except RuntimeError:
                pass  # already tracing


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: Optional[str] = None):
    """Disable recording, print the per-event summary table, optionally
    dump a Chrome trace JSON to profile_path (≙ DisableProfiler
    profiler.h:119 + tools/timeline.py)."""
    global _enabled, _forced, _device_tracing
    if not _enabled:
        return
    _enabled = False
    if _forced:
        _tracing.force_enable(False)
        _forced = False
    was_device = _device_tracing
    _device_tracing = False
    _tracing.annotation_factory = None
    import jax
    try:
        jax.profiler.stop_trace()
    except RuntimeError:
        pass
    if profile_path:
        export_chrome_tracing(
            profile_path,
            device_trace_dir=(_trace_dir or os.environ.get("PTPU_TRACE_DIR"))
            if was_device else None)
    print_profiler_summary(sorted_key or "default")


def _window_spans():
    spans = _tracing.spans(since=_window_mark)
    # the recorder is a bounded ring (PTPU_TRACE_RING, default 65536);
    # a window longer than that has lost its oldest events — say so
    # instead of printing a silently-truncated report (the pre-r12
    # profiler kept an unbounded list)
    if len(spans) >= int(flags.get_flag("trace_ring")):
        print("[profiler] span ring capacity reached: oldest events in "
              "this window were dropped — raise PTPU_TRACE_RING to keep "
              "longer windows")
    return spans


def print_profiler_summary(sorted_key: str = "default"):
    """Aggregate the window's spans by name: calls, total/min/max/avg ms
    (≙ the reference's sorted profiling report, profiler.cc
    PrintProfiler)."""
    enforce(sorted_key in ("default", "calls", "total", "max", "min", "ave"),
            f"invalid sorted_key {sorted_key!r}", exc=InvalidArgumentError)
    agg = _tracing.aggregate(_window_spans())
    if not agg:
        print("[profiler] no events recorded")
        return
    key = {"default": "total_ms", "calls": "calls", "total": "total_ms",
           "max": "max_ms", "min": "min_ms", "ave": "avg_ms"}[sorted_key]
    rows = sorted(agg.items(), key=lambda kv: -kv[1][key])
    hdr = f"{'Event':<44} {'Calls':>7} {'Total(ms)':>11} {'Max':>9} " \
          f"{'Min':>9} {'Ave':>9}"
    print("-" * len(hdr))
    print(hdr)
    print("-" * len(hdr))
    for name, r in rows:
        print(f"{name[:44]:<44} {r['calls']:>7} {r['total_ms']:>11.3f} "
              f"{r['max_ms']:>9.3f} {r['min_ms']:>9.3f} {r['avg_ms']:>9.3f}")
    print("-" * len(hdr))


def _collect_device_trace_events(trace_dir: str):
    """Pull the device timeline out of a jax.profiler dump: the profiler
    writes a Chrome-format *.trace.json.gz under
    <dir>/plugins/profile/<run>/ — merge its events (annotated with the
    RecordEvent names via TraceAnnotation) rather than asking users to
    open TensorBoard separately. ≙ tools/timeline.py merging the CUPTI
    device records into one timeline."""
    import glob
    import gzip
    pats = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not pats:
        return []
    with gzip.open(pats[-1], "rt") as f:
        data = json.load(f)
    out = []
    for ev in data.get("traceEvents", []):
        if not isinstance(ev, dict):
            continue
        # keep metadata ('M': process/thread names) AND timed events; shift
        # every device pid up by 1 so lanes never collide with the host
        # (pid 0) while distinct planes stay distinct
        if "ts" not in ev and ev.get("ph") != "M":
            continue
        ev = dict(ev)
        ev["cat"] = ev.get("cat", "device")
        ev["pid"] = int(ev.get("pid", 0)) + 1
        out.append(ev)
    return out


def export_chrome_tracing(path: str, device_trace_dir: Optional[str] = None):
    """Write the window's host spans — and, when a device trace dir is
    given, the jax.profiler device timeline — as ONE Chrome trace
    (catapult) JSON (≙ tools/timeline.py, which merges host + CUPTI
    device records)."""
    trace = {"traceEvents": _tracing.chrome_trace_events(_window_spans(),
                                                         pid=0),
             "displayTimeUnit": "ms"}
    if device_trace_dir:
        trace["traceEvents"].extend(
            _collect_device_trace_events(device_trace_dir))
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def merge_process_traces(trace_paths, path: str, labels=None):
    """Merge per-process Chrome traces — each produced by
    `export_chrome_tracing` inside one trainer process — into ONE timeline
    with per-process lanes (≙ the reference's tools/timeline.py:24-33,
    whose --profile_path takes a list of per-trainer profile files and
    emits a single catapult view).

    Each input trace's pids are shifted into a disjoint range and labeled
    `rank{r}/host` / `rank{r}/device{k}`, so an N-process world reads as N
    stacked lanes in chrome://tracing / Perfetto."""
    traces = []
    for p in trace_paths:
        with open(p) as f:
            traces.append(json.load(f))
    # pid stride: one disjoint block per rank, wide enough for the
    # largest pid any input trace carries (device-trace planes can be
    # numerous)
    max_pid = 0
    for t in traces:
        for ev in t.get("traceEvents", []):
            if isinstance(ev, dict):
                max_pid = max(max_pid, int(ev.get("pid", 0)))
    stride = max(100, max_pid + 1)
    merged = {"traceEvents": [], "displayTimeUnit": "ms"}
    for r, t in enumerate(traces):
        label = (labels[r] if labels and r < len(labels) else f"rank{r}")
        base = r * stride
        seen = set()
        for ev in t.get("traceEvents", []):
            if not isinstance(ev, dict):
                continue
            ev = dict(ev)
            pid = int(ev.get("pid", 0))
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                # rewritten below with the rank prefix
                continue
            ev["pid"] = base + pid
            seen.add(pid)
            merged["traceEvents"].append(ev)
        for pid in sorted(seen):
            merged["traceEvents"].append({
                "ph": "M", "name": "process_name", "pid": base + pid,
                "args": {"name": label + ("/host" if pid == 0
                                          else f"/device{pid - 1}")}})
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(merged, f)
    return path


@contextmanager
def profiler(state: str = "All", sorted_key: str = "default",
             profile_path: Optional[str] = None,
             trace_dir: Optional[str] = None):
    """Context manager (≙ fluid.profiler.profiler, profiler.py:221):

        with profiler('All', sorted_key='total', profile_path='/tmp/t.json'):
            for batch in data:
                exe.run(...)
    """
    global _trace_dir
    _trace_dir = trace_dir
    reset_profiler()
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key=sorted_key, profile_path=profile_path)
        _trace_dir = None


@contextmanager
def device_tracer(log_dir: str):
    """Capture a device (XPlane) trace to log_dir for TensorBoard — the
    TPU analogue of the CUPTI DeviceTracer (device_tracer.h:49)."""
    global _device_tracing
    import jax
    jax.profiler.start_trace(log_dir)
    _device_tracing = True
    _tracing.annotation_factory = jax.profiler.TraceAnnotation
    try:
        yield
    finally:
        _device_tracing = False
        _tracing.annotation_factory = None
        jax.profiler.stop_trace()


def profiler_enabled() -> bool:
    return _enabled
