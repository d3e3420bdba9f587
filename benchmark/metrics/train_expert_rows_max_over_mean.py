"""The training routing's unevenness: the pairs the fullest (layer, held
expert) got over the mean of all of them, from the counter the step keeps on
the device (`l<i>_moe.rows`, added to in the graph, read from the scope
after the window: no fetch rides the loop). 1 is an even spread. A program
without the counter leaves the metric out."""

UNIT = "x"
SOURCE = "program_counter"
LAYER = "router"
MOVES = "train_tokens_per_s"


def read(run):
    adapter = run.cell.adapter
    counters = adapter.counters(run.cell.config) \
        if hasattr(adapter, "counters") else None
    if counters is None or counters["rows"].mean() <= 0:
        return None
    return float(counters["rows"].max() / counters["rows"].mean())
