"""The latent decode read's share of its roofline: per call (one a layer) the
larger of operations over peak and bytes over bandwidth, at the median decode
tick's live positions (`decode_rows` on `engine/tick`), over the median device
seconds one call took inside the decode tick program (the kernel is found by
its name and its result's shape: benchmark/kernel_ops.py)."""

from .. import kernel_ops
from ..counts import roofline_min_seconds
from ..harness import quantile
from .experts_touched_p50 import decode_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    ticks = [s for s in decode_ticks(run) if "decode_rows" in s.attrs]
    if run.trace is None or not ticks:
        return None
    cell, cfg = run.cell, run.cell.config
    key = kernel_ops.kernel_key(
        "latent_paged_attention", cfg["cache_dtype"],
        (cell.spec["engine"]["n_slots"], cfg["num_attention_heads"],
         cfg["kv_lora_rank"]))
    spent = [t / n for t, n in kernel_ops.per_execution_seconds(run.trace, key)]
    if not spent:
        return None
    flops, nbytes = cell.adapter.mla_call(
        cfg, 1, quantile([s.attrs["decode_rows"] for s in ticks], 0.5))
    least = roofline_min_seconds(flops, nbytes, run.device["peaks"])
    return 100.0 * least / quantile(spent, 0.5)
