"""Share of the traced part in which a collective was in flight on a chip and
no other operation ran there, averaged over the chips."""

UNIT = "%"
SOURCE = "device_trace"
LAYER = "collectives"
MOVES = "train_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    return 100.0 * run.trace.exposed_collective_seconds() / (hi - lo)
