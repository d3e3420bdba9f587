"""The sliding-window decode read's share of its roofline: per call (one a
window layer) the larger of operations over peak and bytes over bandwidth for
the positions the live rows' windows hold (`window_rows` on `engine/tick`:
min(position + 1, window) a live row, 4,096 B a position; the adapter's
`window_decode_call`), over the median device seconds one call took inside the
decode tick program (the kernel is found by its name, `paged_window_attention`,
which a full layer's read does not carry, and its result's shape:
benchmark/kernel_ops.py). Counts and seconds both come from the traced phase's
own decode ticks (metrics/ssm_decode_roofline.py `traced_decode_ticks` says
why). The read takes whole blocks, up to three of 64 positions for a window of
128, and is a few tens of kilobytes a row: the share says how far a read this
small is from what its bytes alone would take. A program without the kernel
or the attr, or an adapter without the counts, leaves the metric out."""

from .. import kernel_ops
from ..counts import roofline_min_seconds
from ..harness import quantile
from .ssm_decode_roofline import traced_decode_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"

ROWS = 8        # a decode row's query heads of one key/value head, padded


def read(run):
    cell, cfg = run.cell, run.cell.config
    call = getattr(cell.adapter, "window_decode_call", None)
    if call is None or run.trace is None:
        return None
    ticks = [s for s in traced_decode_ticks(run) if "window_rows" in s.attrs]
    if not ticks:
        return None
    key = kernel_ops.kernel_key(
        "paged_window_attention", "float32",
        (cell.spec["engine"]["n_slots"], cfg["num_key_value_heads"], ROWS,
         128))
    spent = [t / n for t, n in kernel_ops.per_execution_seconds(run.trace, key)]
    if not spent:
        return None
    flops, nbytes = call(
        cfg, quantile([s.attrs["window_rows"] for s in ticks], 0.5))
    least = roofline_min_seconds(flops, nbytes, run.device["peaks"])
    return 100.0 * least / quantile(spent, 0.5)
