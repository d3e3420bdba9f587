"""The least time a tick could take, which memory bounds: the bytes it cannot
avoid reading (weights as stored, and the keys and values the live requests
have written, averaged over the ticks) over the table's bandwidth, over the
median device time of a tick."""

from ..harness import quantile

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    if run.trace is None or not run.counters.get("ticks"):
        return None
    busy = run.trace.module_busy_seconds()
    if not busy:
        return None
    # a request that has written f positions reads f+1 in its next tick
    live = 0
    for r in run.requests:
        if "shared_len" in r and r["ok"]:
            a, b = r["shared_len"] + 1, r["prompt_len"] + r["n_out"] - 1
            live += (a + b) * (b - a + 1) // 2
    cell = run.cell
    nbytes = cell.adapter.decode_tick_bytes(cell.config, run.counters["n_slots"],
                                            live / run.counters["ticks"])
    least = nbytes / run.device["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / quantile(busy, 0.5)
