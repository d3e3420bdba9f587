"""Median, over the step program's executions in the traced part, of the time
in which one of its operations ran on chip 0."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "device_trace"
LAYER = "model step"
MOVES = "train_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.module_busy_seconds()
    return 1e3 * quantile(busy, 0.5) if busy else None
