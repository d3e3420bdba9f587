"""The least time the chip could take for the fused attention calls of one
training step (per call the larger of operations over peak and bytes over
bandwidth, from shapes: benchmark/counts.py) over the device time the Mosaic
kernels took inside one execution of the step program on chip 0 (median over
the traced executions). The kernels are found as custom calls to
tpu_custom_call; the train step has no other."""

from ..counts import roofline_min_seconds
from ..harness import quantile

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "train_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    spent = [x for x in run.trace.module_mosaic_seconds() if x > 0]
    if not spent:
        return None
    cell = run.cell
    calls = cell.adapter.flash_calls(cell.config, cell.traffic,
                                     cell.traffic["batch_per_chip"])
    least = sum(roofline_min_seconds(f, b, run.device["peaks"])
                for f, b in calls)
    return 100.0 * least / quantile(spent, 0.5)
