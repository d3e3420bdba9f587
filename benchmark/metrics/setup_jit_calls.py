"""The small jitted calls set-up traced: the sum of `jits` over set-up's kept
`compile` spans (`setup_trace_s.py`): inside a program's first run the trace
events nested in its outermost trace, outside any span every trace event.
Each is a cache look-up and a trace of its own."""

from .setup_trace_s import total

UNIT = "calls"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    return total(run, "jits")
