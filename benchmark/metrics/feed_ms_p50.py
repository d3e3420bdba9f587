"""Median of the program's `executor/feed` spans: host arrays to device arrays
and the gathering of state, once a step."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "input feed"
MOVES = "train_tokens_per_s"


def read(run):
    return quantile(run.span_ms("executor/feed"), 0.5)
