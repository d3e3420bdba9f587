"""Busy slot-ticks over all slot-ticks between the window's opening and
--seconds later (the engine's own counters, as engine.occupancy() divides them)."""

UNIT = "%"
SOURCE = "program_counter"
LAYER = "scheduler"
MOVES = "ttft_p90_ms"


def read(run):
    c = run.counters
    if not c.get("total_slot_ticks_window"):
        return None
    return 100.0 * c["busy_slot_ticks_window"] / c["total_slot_ticks_window"]
