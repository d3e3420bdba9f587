"""Process start to the window's opening: build, initialise the weights on the
device from the seed, compile or load from the cache, warm the cell's shapes."""

UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
