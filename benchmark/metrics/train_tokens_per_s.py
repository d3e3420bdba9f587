"""Tokens of all the steps the window sent, over the time from the first one's
dispatch to the last one's loss on the host (the loop keeps steps in flight
and waits for every one it sent: loops/train.py). The language model counts
every position, the translation model its non-padding target tokens."""

UNIT = "tokens/s"
SOURCE = "host_clock"


def read(run):
    if not run.steps:
        return None
    return sum(n for _, _, n in run.steps) / (run.steps[-1][1] - run.steps[0][0])
