"""Tokens of the whole steps completed, over the time from the first of those
steps' start to the last one's loss on the host. The language model counts
every position, the translation model its non-padding target tokens."""

UNIT = "tokens/s"
SOURCE = "host_clock"


def read(run):
    if not run.steps:
        return None
    return sum(n for _, _, n in run.steps) / (run.steps[-1][1] - run.steps[0][0])
