"""Median over the window's decode ticks (`engine/tick` spans with no slot in
prefill) of `experts_touched`: over the routed layers, the held experts that
got at least one row, which is how many experts' weights the tick streamed
(12 a layer are held). A program without the attr leaves the metric out."""

from ..harness import quantile

UNIT = "experts"
SOURCE = "program_span"
LAYER = "router"
MOVES = "tpot_p50_ms"


def decode_ticks(run):
    return [s for s in run.spans if s.name == "engine/tick"
            and "experts_touched" in s.attrs and not s.attrs.get("prefill")]


def read(run):
    return quantile([s.attrs["experts_touched"] for s in decode_ticks(run)],
                    0.5)
