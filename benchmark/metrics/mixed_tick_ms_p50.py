"""Median of the program's `engine/tick` spans whose `mixed` is 1: the ticks
that ran the mixed program, a prompt chunk in a lane beside the decode rows.
A first token is the wait for the running tick and then such ticks."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(run):
    return quantile([s.duration_ms for s in run.spans
                     if s.name == "engine/tick" and s.attrs.get("mixed") == 1],
                    0.5)
