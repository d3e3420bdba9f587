"""Median over the window's `engine/tick` spans of `state_rows`: the live
decode rows whose state-space state the tick reads and writes (a slot in
prefill moves its state in a lane), the count the two decode kernels' shares
and the tick's byte count stand on. A program without the attr (it is new)
leaves the metric out."""

from ..harness import quantile

UNIT = "rows"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"


def read(run):
    return quantile([s.attrs["state_rows"] for s in run.spans
                     if s.name == "engine/tick" and "state_rows" in s.attrs],
                    0.5)
