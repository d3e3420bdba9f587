"""The routed layers' grouped products of a TRAINING step against their
roofline: per layer 3 matmuls x 6 operations a parameter a HELD (row,
expert) pair, the held experts' weights read once forward and twice
backward plus the pairs' rows (benchmark/models/mellum.py
`experts_train_call`), at the pairs the step's own counter says a step held
on average, over the device time the products' Mosaic calls (megablox's
`gmm` and `tgmm`, six a layer: the trace keeps the kernels' own names) took
together inside one execution of the step program on chip 0 (median over the
traced executions). A program without them leaves the metric out."""

from ..counts import roofline_min_seconds
from ..harness import quantile

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "train_tokens_per_s"


def read(run):
    adapter, cfg = run.cell.adapter, run.cell.config
    if run.trace is None or not hasattr(adapter, "experts_train_call"):
        return None
    spent = adapter.kernel_seconds(run.trace, adapter.is_grouped_product)
    counters = adapter.counters(cfg)
    if not spent or counters is None:
        return None
    # as floats: the counters are int32 and a step's pairs times a run's
    # held pairs passes 2**31
    routed, held = (counters["pairs"][:, i].astype(float) for i in (0, 1))
    tokens = run.cell.traffic["batch_per_chip"] * run.cell.traffic["seq_len"]
    per_step = tokens * cfg["num_experts_per_tok"]      # pairs routed a step
    least = sum(roofline_min_seconds(
        *adapter.experts_train_call(cfg, per_step * h / max(r, 1)),
        run.device["peaks"]) for r, h in zip(routed, held))
    return 100.0 * least / quantile(spent, 0.5)
