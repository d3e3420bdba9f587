"""Seconds of set-up that JAX reports as retrieving executables from the
persistent compile cache: the sum of `cache_load_s` over set-up's kept
`compile` spans (`setup_trace_s.py`). 0 in a run that compiled everything."""

from .setup_trace_s import total

UNIT = "s"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    return total(run, "cache_load_s")
