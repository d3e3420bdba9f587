"""Median over the window's decode ticks (`engine/tick` spans with no slot in
prefill) of `expert_runs`: over the routed layers, the maximal runs of touched
experts in the held experts' stored order. A run ends at a touched expert
whose neighbour no row selected: where the schedule before PR 51 let the
weight stream drain, so a later reader can put a tick's edges beside the
expert kernels' time. A program without the attr leaves the metric out."""

from ..harness import quantile
from .experts_touched_p50 import decode_ticks

UNIT = "runs"
SOURCE = "program_span"
LAYER = "router"
MOVES = "tpot_p50_ms"


def read(run):
    return quantile([s.attrs["expert_runs"] for s in decode_ticks(run)
                     if "expert_runs" in s.attrs], 0.5)
