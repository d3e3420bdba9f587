"""The latent expert product's share of its roofline over one decode tick
(`moe_experts_roofline`'s arithmetic for an expert of two matrices on a latent
row: that reader keys its call on `hidden_size` and cannot find a product
whose rows are `moe_latent_size` wide): the larger of operations over peak
(the routed (row, expert) pairs' two matmuls) and bytes over bandwidth (the
touched experts' weights, the latent rows in and out), at the median decode
tick's counts, over the median device seconds the kernel's calls took together
inside one execution of the decode tick program (one call a routed layer;
found by its name and its result's shape). Counts and seconds come from the
same ticks, the traced phase's (`ssm_decode_roofline.traced_decode_ticks`).
A program without the kernel leaves the metric out."""

from .. import kernel_ops
from ..counts import roofline_min_seconds
from ..harness import quantile
from .ssm_decode_roofline import traced_decode_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    cell, cfg = run.cell, run.cell.config
    if run.trace is None or "moe_latent_size" not in cfg:
        return None
    ticks = traced_decode_ticks(run)
    if not ticks:
        return None
    n_rows = cell.spec["engine"]["n_slots"]
    key = kernel_ops.kernel_key("latent_experts", "float32",
                                (n_rows, cfg["moe_latent_size"]))
    spent = [t for t, _ in kernel_ops.per_execution_seconds(run.trace, key)]
    if not spent:
        return None
    flops, nbytes = cell.adapter.experts_call(
        cfg, n_rows,
        quantile([s.attrs["experts_touched"] for s in ticks], 0.5),
        quantile([s.attrs["routed_rows"] for s in ticks], 0.5))
    least = roofline_min_seconds(flops, nbytes, run.device["peaks"])
    return 100.0 * least / quantile(spent, 0.5)
