"""Median of the program's `engine/tick` spans."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "model step"
MOVES = "tpot_p50_ms"


def read(run):
    return quantile(run.span_ms("engine/tick"), 0.5)
