"""The walk over the touched experts' share of its roofline over the ticks
that ran under the profiler: the sum over those ticks of the walk's least
time (the larger of bytes over bandwidth and operations over peak: the
touched experts' weights once, the routed (row, expert) pairs' three
matmuls, the tick's rows in and out a routed layer; the adapter's
`experts_call` at the tick's OWN `experts_touched`, `routed_rows` and rows),
over the device seconds the kernel's calls took inside those ticks' programs
(one call a routed layer, in the decode tick over the slots and in the mixed
tick over the slots and the lanes: every Mosaic call made in the scope
`moe_experts`, whatever its result's shape). `moe_experts_roofline` divides
the WINDOW's median counts by the traced phase's median seconds and passes
100% where the live rows climb through the window (PERF.md section 7); here
counts and seconds come from the SAME ticks and are SUMMED: the trace holds N
executions of the tick programs, and the counts are those of the LAST N
`engine/tick` spans of the program's ring (`hybrid_tick_roofline`'s rule). A
tick's rows are the engine's slots and, in a mixed tick, the lanes' tokens it
carried (`prefill_tokens`: no more than the lanes hold, so the least time is
not counted too high). A traced tick without its counts is left out WITH its
own execution's seconds; a program without the kernel or the counts, or an
adapter without them, leaves the metric out."""

import bisect

from ..counts import roofline_min_seconds
from .hybrid_tick_roofline import tick_modules, traced_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"

SCOPE = "moe_experts_custom-call_"
ATTRS = ("experts_touched", "routed_rows")


def read(run):
    cell, cfg = run.cell, run.cell.config
    call = getattr(cell.adapter, "experts_call", None)
    if call is None or run.trace is None or not run.trace.devices:
        return None
    dev = run.trace.devices[0]
    names = set(tick_modules(run.trace))
    inside = sorted((s, e) for s, e, name, _ in dev.modules if name in names)
    starts = [s for s, _ in inside]
    spent = [0.0] * len(inside)         # the walk's seconds, an execution
    for s, e, key, _, mosaic in dev.ops:
        if mosaic and key.startswith(SCOPE):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < inside[i][1]:
                spent[i] += e - s
    # the k-th span from the end is the k-th execution from the end; a tick
    # whose counts never came back (they ride on the NEXT tick's read: the
    # phase's last tick has none) is left out with its own seconds
    pairs = [(s, t) for s, t in zip(reversed(traced_ticks(run, len(inside))),
                                    reversed(spent))
             if all(a in s.attrs for a in ATTRS)]
    seconds = sum(t for _, t in pairs)
    if not seconds:
        return None
    n_slots = cell.spec["engine"]["n_slots"]
    least = sum(roofline_min_seconds(
        *call(cfg, n_slots + s.attrs.get("prefill_tokens", 0),
              s.attrs["experts_touched"], s.attrs["routed_rows"]),
        run.device["peaks"]) for s, _ in pairs)
    return 100.0 * least / seconds
