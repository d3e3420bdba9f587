"""Seconds of set-up inside XLA's compiler: the sum of `compile_s` over set-up's
kept `compile` spans (`setup_trace_s.py`), JAX's backend-compile events LESS
the cache retrieval reported inside them. About 0 in a run that finds every
executable in the compile cache; above that the cache missed, and the kept
spans say on which `program`."""

from .setup_trace_s import total

UNIT = "s"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    return total(run, "compile_s")
