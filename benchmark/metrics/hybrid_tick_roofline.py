"""The WHOLE tick's share of its roofline, decode ticks and mixed ticks alike,
over the ticks that ran under the profiler: the sum over those ticks of each
tick's least time, the larger of bytes over bandwidth and operations over
peak, over the device seconds the tick programs were busy in the trace. A
tick's counts are the adapter's `hybrid_tick_counts` of what its `engine/tick`
span counted (`state_rows`, `prefill_tokens`, `kv_blocks`, `lane_kv_blocks`):
every parameter once, the live rows' state read and written, the K/V blocks
read, 2 x parameters x rows, the attention and scan products; they stay the
same whatever implements the work. Counts and seconds come from the SAME
ticks: the trace holds N executions of the tick programs (the decode tick and
the mixed tick: the programs with the most device time), and the counts are
those of the LAST N `engine/tick` spans of the program's ring (the traced
phase ends the run). A reader's test hands over the spans it means
(`run.spans`, where the ring holds nothing newer). A program without the
attrs, or an adapter without the counts, leaves the metric out."""

from ..counts import roofline_min_seconds

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"

ATTRS = ("state_rows", "prefill_tokens", "kv_blocks", "lane_kv_blocks")


def tick_modules(trace):
    """Names of the tick programs: every program whose executions on chip 0
    took at least a twentieth of the busiest one's time together."""
    total = {}
    for s, e, name, _ in trace.devices[0].modules if trace.devices else []:
        total[name] = total.get(name, 0.0) + e - s
    most = max(total.values(), default=0.0)
    return [n for n, t in total.items() if t >= most / 20]


def traced_ticks(run, n):
    """The last `n` `engine/tick` spans the program recorded (the traced
    phase ends the run); the window's where the ring holds nothing newer."""
    from paddle_tpu.observability import tracing
    window = [s for s in run.spans if s.name == "engine/tick"]
    newest = max((s.start for s in window if hasattr(s, "start")),
                 default=None)
    later = sorted((s for s in tracing.spans() if s.name == "engine/tick"
                    and newest is not None and s.start > newest),
                   key=lambda s: s.start)
    return later[-n:] if later else window[-n:]


def read(run):
    counts = getattr(run.cell.adapter, "hybrid_tick_counts", None)
    if counts is None or run.trace is None or not run.trace.devices:
        return None
    busy = [t for name in tick_modules(run.trace)
            for t in run.trace.module_busy_seconds(name)]
    ticks = [s for s in traced_ticks(run, len(busy))
             if all(a in s.attrs for a in ("state_rows", "kv_blocks"))]
    if not busy or not ticks:
        return None
    block = run.cell.spec["engine"]["block_size"]
    least = sum(roofline_min_seconds(
        *counts(run.cell.config, *(s.attrs.get(a, 0) for a in ATTRS), block),
        run.device["peaks"]) for s in ticks)
    # where fewer spans than executions carry the counts (the parent of the
    # PR that added them carries none), the seconds are scaled to the ticks
    # that do
    return 100.0 * least / (sum(busy) * len(ticks) / len(busy))
