"""Seconds of set-up that JAX reports as lowering a traced program to an MLIR
module: the sum of `lower_s` over set-up's kept `compile` spans
(`setup_trace_s.py`). The startup program's share is its random generators'
lowering."""

from .setup_trace_s import total

UNIT = "s"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    return total(run, "lower_s")
