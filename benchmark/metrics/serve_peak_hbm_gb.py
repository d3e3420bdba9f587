"""Peak device memory of the chip, in 1e9 bytes: memory_stats()'s
peak_bytes_in_use (arrays) + peak_bytes_reserved (the scratch of compiled
programs), as harness.memory_peak_bytes reads them."""

from ..harness import memory_peak_bytes

UNIT = "GB"
SOURCE = "program_counter"
LAYER = "device"
MOVES = "tpot_p50_ms"


def read(run):
    return memory_peak_bytes(run.device["devices"]) / 1e9
