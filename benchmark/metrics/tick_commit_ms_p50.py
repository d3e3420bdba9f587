"""Median over the benchmark's `benchmark/engine.step` spans of the
`engine/commit` and `engine/finish` spans directly under it: the host's work
after the tick's tokens are back, before the next tick can be filled."""

from collections import defaultdict

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"


def read(run):
    after = defaultdict(float)
    for s in run.spans:
        if s.name in ("engine/commit", "engine/finish"):
            after[s.parent_id] += s.duration_ms
    if not after:       # a program without the spans (or without span ids)
        return None
    return quantile([after[s.id] for s in run.spans
                     if s.name == "benchmark/engine.step" and s.id in after],
                    0.5)
