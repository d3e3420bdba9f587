"""The routing's unevenness over the window: the rows the fullest (layer, held
expert) got over the mean of all of them, from the engine's per-expert row
counter (`stats()["expert_rows"]`) as each tick adds to it: the tick's counts
ride its `engine/tick` span (`expert_rows`), which is how a reader sums the
window alone. 1 is an even spread. A program without the counter leaves the
metric out."""

import numpy as np

UNIT = "x"
SOURCE = "program_counter"
LAYER = "router"
MOVES = "tpot_p50_ms"


def read(run):
    rows = [s.attrs["expert_rows"] for s in run.spans
            if s.name == "engine/tick" and "expert_rows" in s.attrs]
    if not rows:
        return None
    total = np.asarray(rows, np.float64).sum(axis=0)
    return float(total.max() / total.mean()) if total.mean() > 0 else None
