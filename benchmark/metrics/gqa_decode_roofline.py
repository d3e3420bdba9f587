"""The grouped-query paged decode read's share of its roofline: per call (one
an attention layer) the larger of operations over peak and bytes over
bandwidth, at the median decode tick's live blocks (`kv_blocks` on
`engine/tick`: the read takes whole blocks), over the median device seconds
one call took inside the decode tick program (the kernel is found by its name
and its result's shape: benchmark/kernel_ops.py; the counts are the
adapter's `gqa_decode_call`). A program without the kernel, or an adapter
without the counts, leaves the metric out."""

from .. import kernel_ops
from ..counts import roofline_min_seconds
from ..harness import quantile
from .experts_touched_p50 import decode_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"

ROWS = 8        # a decode row's query heads of one key/value head, padded


def read(run):
    cell, cfg = run.cell, run.cell.config
    call = getattr(cell.adapter, "gqa_decode_call", None)
    ticks = [s for s in decode_ticks(run) if "kv_blocks" in s.attrs]
    if call is None or run.trace is None or not ticks:
        return None
    key = kernel_ops.kernel_key(
        "paged_gqa_attention", "float32",
        (cell.spec["engine"]["n_slots"], cfg["num_key_value_heads"], ROWS,
         128))
    spent = [t / n for t, n in kernel_ops.per_execution_seconds(run.trace, key)]
    if not spent:
        return None
    flops, nbytes = call(
        cfg, quantile([s.attrs["kv_blocks"] for s in ticks], 0.5),
        cell.spec["engine"]["block_size"])
    least = roofline_min_seconds(flops, nbytes, run.device["peaks"])
    return 100.0 * least / quantile(spent, 0.5)
