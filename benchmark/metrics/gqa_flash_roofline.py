"""The flash kernels of the FULL grouped-query layers against their roofline:
as `window_flash_roofline`, for the calls whose names carry a head group and
no window (`..._g<group>`): the causal triangle's pairs, K and V counted once
a group of query heads. A program without such kernels leaves the metric
out."""

from . import window_flash_roofline

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "train_tokens_per_s"


def read(run):
    return window_flash_roofline.read(run, kind="full")
