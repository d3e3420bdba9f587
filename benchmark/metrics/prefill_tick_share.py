"""Busy slot-ticks that fed a prompt token over all busy slot-ticks, window and
drain. A request feeds one prompt token per tick from its shared length on."""

UNIT = "%"
SOURCE = "program_counter"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(run):
    busy = run.counters.get("busy_slot_ticks")
    if not busy:
        return None
    fed = sum(r["prompt_len"] - r["shared_len"] for r in run.requests
              if "shared_len" in r)
    return 100.0 * fed / busy
