"""Programs that ran for the first time during set-up: the
`executor/compile_or_load` spans among set-up's kept `compile` spans
(`setup_trace_s.py`). Two in a training cell (the startup program, the step)
if nothing else compiles; a serving cell's ticks, one a program. A jitted
function called outside a compiled step (a weight builder's) has no such span
and counts under the `jax/unscoped` record's seconds."""

from .setup_trace_s import setup_spans

UNIT = "programs"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    spans = setup_spans(run)
    if spans is None:
        return None
    return sum(s.name == "executor/compile_or_load" for s in spans)
