"""The state-space decode update's share of its roofline over one decode tick:
the larger of bytes over bandwidth (the live rows' state read AND written, a
float32 h of heads x head_dim x state a layer, and the rows' x, B, C, dt in
and y out) and operations over peak, at the median decode tick's live rows
(`active` on `engine/tick`: a decode tick has no slot in prefill, so every
active slot feeds a decode row), over the median device seconds the kernel's
calls took together inside one execution of the decode tick program (one call
a state-space layer; found by its name and its first result's shape:
benchmark/kernel_ops.py; the counts are the adapter's `ssm_decode_call`). The
kernel's time moves with the live rows, row for row, so counts and seconds
have to come from the SAME ticks: the decode ticks of the traced phase's last
`trace_seconds` (`traced_decode_ticks`: the program's span ring still holds
them), not the window's, whose load the traced phase only approaches (read
against the window's median the share came out 105% with a fifth fewer rows
live under the profiler). A program without the kernel, or an adapter without
the counts, leaves the metric out."""

from .. import kernel_ops
from ..counts import roofline_min_seconds
from ..harness import quantile
from .experts_touched_p50 import decode_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def traced_decode_ticks(run):
    """The decode ticks that ran under the profiler: those of the program's
    span ring that began in the last `trace_seconds` before its newest tick
    (the traced phase ends the run). The window's, where the ring holds
    nothing newer (a reader's test hands over the spans it means)."""
    from paddle_tpu.observability import tracing
    window = decode_ticks(run)
    newest = max((s.start for s in window if hasattr(s, "start")),
                 default=None)
    later = [s for s in tracing.spans() if s.name == "engine/tick"
             and "experts_touched" in s.attrs and not s.attrs.get("prefill")
             and newest is not None and s.start > newest]
    if not later:
        return window
    first = max(s.start for s in later) - run.cell.spec["trace_seconds"]
    return [s for s in later if s.start >= first]


def read(run):
    cell, cfg = run.cell, run.cell.config
    call = getattr(cell.adapter, "ssm_decode_call", None)
    if call is None or run.trace is None:
        return None
    ticks = [s for s in traced_decode_ticks(run) if "active" in s.attrs]
    if call is None or run.trace is None or not ticks:
        return None
    key = kernel_ops.kernel_key(
        "ssm_decode_update", "float32",
        (cell.spec["engine"]["n_slots"], cfg["mamba_num_heads"], 1,
         cfg["mamba_head_dim"]))
    spent = [t for t, _ in kernel_ops.per_execution_seconds(run.trace, key)]
    if not spent:
        return None
    flops, nbytes = call(cfg, quantile([s.attrs["active"] for s in ticks], 0.5))
    least = roofline_min_seconds(flops, nbytes, run.device["peaks"])
    return 100.0 * least / quantile(spent, 0.5)
