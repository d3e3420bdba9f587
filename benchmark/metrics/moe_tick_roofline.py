"""The whole decode tick's share of what memory bounds: the bytes it cannot
avoid reading (every parameter outside the routed experts, the experts the
median decode tick touched, the live latent rows in every layer) over the
table's bandwidth, over the median device time of a tick
(tick_device_ms_p50's). The counts are the window's (`experts_touched`,
`decode_rows` on `engine/tick`), the time the traced part's. A program without
the attrs leaves the metric out."""

from ..harness import quantile
from .experts_touched_p50 import decode_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    ticks = [s for s in decode_ticks(run) if "decode_rows" in s.attrs]
    if run.trace is None or not ticks:
        return None
    busy = run.trace.module_busy_seconds()
    if not busy:
        return None
    cell = run.cell
    nbytes = cell.adapter.moe_tick_bytes(
        cell.config, cell.spec["engine"]["n_slots"],
        quantile([s.attrs["experts_touched"] for s in ticks], 0.5),
        quantile([s.attrs["decode_rows"] for s in ticks], 0.5))
    least = nbytes / run.device["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / quantile(busy, 0.5)
