"""Median over the program's `engine/tick` spans of their `kv_blocks` count:
the live K/V blocks the tick's slots map, sum over slots of ceil((fed+1) /
block size), counted where the engine walks the block tables to fill the
tick's feeds. Times a block's bytes (K and V) and the layers it is the K/V a
tick really reads: the check of decode_tick_roofline's numerator from inside.
A program without the count (the attr is new) leaves the metric out."""

from ..harness import quantile

UNIT = "blocks"
SOURCE = "program_span"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    counts = [s.attrs["kv_blocks"] for s in run.spans
              if s.name == "engine/tick" and "kv_blocks" in s.attrs]
    return quantile(counts, 0.5)
