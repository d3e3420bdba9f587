"""Median over the program's `engine/tick` spans of their `prefill_tokens`
count, over the ticks where it is above 0: the prompt tokens one tick's
prefill lanes consumed, counted where the engine fills the lanes. With one
request prefilling at a time it is the median unshared prompt of the mix.
A program that feeds one prompt token a tick carries no such count (the attr
is new): the metric is left out."""

from ..harness import quantile

UNIT = "tokens"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(run):
    counts = [s.attrs["prefill_tokens"] for s in run.spans
              if s.name == "engine/tick"
              and s.attrs.get("prefill_tokens", 0) > 0]
    return quantile(counts, 0.5)
