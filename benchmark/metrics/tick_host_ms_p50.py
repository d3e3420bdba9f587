"""Median of the program's `engine/dispatch` spans: the host's part of a tick
before the device call returns."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "tpot_p50_ms"


def read(run):
    return quantile(run.span_ms("engine/dispatch"), 0.5)
