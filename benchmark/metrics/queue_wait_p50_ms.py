"""Median of GenRequest.admitted_pc - submitted_pc: the wait for a slot and
for blocks."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_counter"
LAYER = "scheduler"
MOVES = "ttft_p90_ms"


def read(run):
    waits = [r["admitted"] - r["submitted"] for r in run.requests
             if r.get("admitted") is not None]
    return 1e3 * quantile(waits, 0.5) if waits else None
