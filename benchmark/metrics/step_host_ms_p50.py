"""Median over the benchmark's `benchmark/step` spans of the span's length less
the waits for a loss directly under it (the loop's `benchmark/loss_wait` for
an earlier step's loss, or the program's `executor/fetch`): the host's part of
a step. Only the loop's turns that waited are counted: the first ones, which
fill the pipeline, have no wait under them."""

from collections import defaultdict

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "executor step"
MOVES = "train_tokens_per_s"


def read(run):
    fetch = defaultdict(float)
    for s in run.spans:
        if s.name in ("executor/fetch", "benchmark/loss_wait"):
            fetch[s.parent_id] += s.duration_ms
    if not fetch:       # a program without the span (or without span ids)
        return None
    return quantile([s.duration_ms - fetch[s.id] for s in run.spans
                     if s.name == "benchmark/step" and s.id in fetch], 0.5)
