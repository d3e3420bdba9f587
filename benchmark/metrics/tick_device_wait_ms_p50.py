"""Median of the program's `engine/device_wait` spans: on the ticks that realize
their ids in two parts (one in sixteen: two parts cost the thread a second
sleep and wake-up), inside `engine/wait`: the ids' copy back enqueued, then
the time until the tick's last op on the device is done (`block_until_ready`)
and the thread knows it. What is left of the device's tick once the host's
dispatch has run beside it, and the wake-up after it."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "model step"
MOVES = "tpot_p50_ms"


def read(run):
    return quantile(run.span_ms("engine/device_wait"), 0.5)
