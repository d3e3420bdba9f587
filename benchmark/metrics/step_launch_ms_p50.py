"""Median of the program's `executor/run` spans: the call of the compiled step
up to its return, which is the launch and not the step (the device runs on)."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "executor step"
MOVES = "train_tokens_per_s"


def read(run):
    return quantile(run.span_ms("executor/run"), 0.5)
