"""Median of the self time of the `benchmark/step` spans: host time of a step
that no span of the program covers. The subtraction is the program's
(`tracing.self_time_ms`); a program without it has nothing to read."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "executor step"
MOVES = "train_tokens_per_s"


def read(run):
    from paddle_tpu.observability import tracing
    if not hasattr(tracing, "self_time_ms"):
        return None
    return quantile(tracing.self_time_ms(run.spans, "benchmark/step"), 0.5)
