"""Median of the program's `executor/lookup` spans: batch mask, fetch names,
validation of the fetches and the look-up of the compiled step, once a step."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "executor step"
MOVES = "train_tokens_per_s"


def read(run):
    return quantile(run.span_ms("executor/lookup"), 0.5)
