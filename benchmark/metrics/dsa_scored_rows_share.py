"""The rows the sparse latent read's selection SORTED over the rows that held
a token, over the window's ticks: the sum of `engine/tick`'s
`dsa_scored_rows` (the rows the op's selection reached in that tick,
`fusion/sparse_latent_attention.py` `rung`: the tick's live decode and lane
rows in whole steps of 8) over the sum of `dsa_rows`. 100% is a selection
that gathered, scored and sorted exactly its live rows; a program that ran it
for every slot and lane row, had it the counter, would read ~900% on a decode
tick of 7 live rows of 64 and ~200% on a mixed tick. A program without the
counter leaves the metric out."""

UNIT = "%"
SOURCE = "program_counter"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    ticks = [s.attrs for s in run.spans if s.name == "engine/tick"
             and "dsa_rows" in s.attrs and "dsa_scored_rows" in s.attrs]
    rows = sum(a["dsa_rows"] for a in ticks)
    if not rows:
        return None
    return 100.0 * sum(a["dsa_scored_rows"] for a in ticks) / rows
