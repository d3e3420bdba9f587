"""1 - the union of device operations over the traced part, averaged over the
chips."""

UNIT = "%"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "train_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - run.trace.busy_seconds(lo, hi) / (hi - lo))
