"""The grouped expert product's share of its roofline over one decode tick:
the larger of operations over peak (the routed (row, expert) pairs' three
matmuls) and bytes over bandwidth (the touched experts' weights, the rows in
and out), at the median decode tick's counts, over the median device seconds
the kernel's calls took together inside one execution of the decode tick
program (one call a routed layer; found by its name and its result's shape)."""

from .. import kernel_ops
from ..counts import roofline_min_seconds
from ..harness import quantile
from .experts_touched_p50 import decode_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    ticks = decode_ticks(run)
    if run.trace is None or not ticks:
        return None
    cell, cfg = run.cell, run.cell.config
    n_rows = cell.spec["engine"]["n_slots"]
    key = kernel_ops.kernel_key("moe_experts", "float32",
                                (n_rows, cfg["hidden_size"]))
    spent = [t for t, _ in kernel_ops.per_execution_seconds(run.trace, key)]
    if not spent:
        return None
    flops, nbytes = cell.adapter.experts_call(
        cfg, n_rows,
        quantile([s.attrs["experts_touched"] for s in ticks], 0.5),
        quantile([s.attrs["routed_rows"] for s in ticks], 0.5))
    least = roofline_min_seconds(flops, nbytes, run.device["peaks"])
    return 100.0 * least / quantile(spent, 0.5)
