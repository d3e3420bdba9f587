"""The flash kernels of the sliding-window layers against their roofline: the
least time the chip could take for their forward and backward calls of one
training step (per call the larger of operations over peak and bytes over
bandwidth, from the LIVE (query, key) pairs a window leaves and K and V
counted once a group of query heads: benchmark/models/mellum.py
`flash_calls`), over the device time the kernels whose names end in the
window (`..._w<window>`, ops/pallas_kernels.py FlashPlan.scope) took inside
one execution of the step program on chip 0 (median over the traced
executions). A kernel that walks the whole causal triangle reads under a
quarter. A program without such kernels leaves the metric out."""

from ..counts import roofline_min_seconds
from ..harness import quantile

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
KIND = "window"


def read(run, kind=KIND):
    cell = run.cell
    adapter, cfg = cell.adapter, cell.config
    if run.trace is None or not hasattr(adapter, "flash_calls") \
            or not hasattr(adapter, "is_flash"):
        return None
    spent = adapter.kernel_seconds(
        run.trace, lambda key: adapter.is_flash(key, cfg, kind))
    if not spent:
        return None
    calls = adapter.flash_calls(cfg, cell.traffic,
                                cell.traffic["batch_per_chip"], kind)
    least = sum(roofline_min_seconds(f, b, run.device["peaks"])
                for f, b in calls)
    return 100.0 * least / quantile(spent, 0.5)
