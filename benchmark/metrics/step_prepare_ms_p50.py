"""Median of the program's `parallel/prepare` spans: what ParallelExecutor.run
does on the host before it hands the step to Executor.run."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "input feed"
MOVES = "train_tokens_per_s"


def read(run):
    return quantile(run.span_ms("parallel/prepare"), 0.5)
