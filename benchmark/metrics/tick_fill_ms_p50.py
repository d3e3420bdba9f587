"""Median of the program's `engine/fill_feeds` spans: token and position rows,
block tables and the sanitizer's notes, written on the host before a tick."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "tpot_p50_ms"


def read(run):
    return quantile(run.span_ms("engine/fill_feeds"), 0.5)
