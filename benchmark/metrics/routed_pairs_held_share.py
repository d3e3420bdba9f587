"""Of the (row, expert) pairs the training routers made, the share that
landed on experts this chip holds (and so were computed here): the step's
`l<i>_moe.pairs` counters (routed, held, dropped), summed over the layers.
One rank of four under an even routing reads 25; a rank trained alone on a
stream that repeats reads more, and its step slows with it (lower is
better: PERF.md section 6, PR 50). A program without the counter leaves the
metric out."""

UNIT = "%"
SOURCE = "program_counter"
LAYER = "router"
MOVES = "train_tokens_per_s"


def read(run):
    adapter = run.cell.adapter
    counters = adapter.counters(run.cell.config) \
        if hasattr(adapter, "counters") else None
    if counters is None or counters["pairs"][:, 0].sum() <= 0:
        return None
    routed, held = counters["pairs"][:, 0].sum(), counters["pairs"][:, 1].sum()
    return 100.0 * float(held) / float(routed)
