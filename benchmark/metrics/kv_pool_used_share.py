"""Peak of allocated blocks (pager.pool.n_used, read after every tick) over
n_blocks."""

UNIT = "%"
SOURCE = "program_counter"
LAYER = "pager"
MOVES = "ttft_p90_ms"


def read(run):
    c = run.counters
    if not c.get("n_blocks"):
        return None
    return 100.0 * c["peak_blocks_used"] / c["n_blocks"]
