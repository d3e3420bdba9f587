"""Median over the benchmark's `benchmark/step` spans of the span's length less
the `executor/fetch` spans directly under it: the host's serial part of a step,
the wait for the loss taken out."""

from collections import defaultdict

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "executor step"
MOVES = "train_tokens_per_s"


def read(run):
    fetch = defaultdict(float)
    for s in run.spans:
        if s.name == "executor/fetch":
            fetch[s.parent_id] += s.duration_ms
    if not fetch:       # a program without the span (or without span ids)
        return None
    return quantile([s.duration_ms - fetch[s.id] for s in run.spans
                     if s.name == "benchmark/step" and s.id in fetch], 0.5)
