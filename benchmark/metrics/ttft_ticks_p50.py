"""Median over the window's `request/prefill` spans of their `ticks`: the
engine ticks from the one that admitted the request to the one that emitted
its first token, inclusive (`GenRequest.ticks_to_first`, counted in the
scheduler)."""

from ..harness import quantile

UNIT = "ticks"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(run):
    return quantile([s.attrs["ticks"] for s in run.spans
                     if s.name == "request/prefill" and "ticks" in s.attrs],
                    0.5)
