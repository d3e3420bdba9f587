"""Median of the benchmark's own span around one Executor.run, feed and fetch
included."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "executor step"
MOVES = "train_tokens_per_s"


def read(run):
    return quantile(run.span_ms("benchmark/step"), 0.5)
