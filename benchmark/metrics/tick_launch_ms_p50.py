"""Median of the program's `engine/launch` spans: the bound step's call, which
puts the feeds on the device, launches the tick and writes the scope back."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "tpot_p50_ms"


def read(run):
    return quantile(run.span_ms("engine/launch"), 0.5)
