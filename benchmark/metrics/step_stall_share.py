"""Share of the window's step time spent in steps longer than 1.5 x the median
step: what stalls (collector, allocator, a late host) cost."""

from ..harness import quantile

UNIT = "%"
SOURCE = "program_span"
LAYER = "executor step"
MOVES = "train_tokens_per_s"


def read(run):
    ms = run.span_ms("benchmark/step")
    if not ms:
        return None
    limit = 1.5 * quantile(ms, 0.5)
    return 100.0 * sum(x for x in ms if x > limit) / sum(ms)
