"""Mean over the program's `engine/tick` spans of their `prefill` count: the
slots that fed a prompt token whose output is dropped, counted by the
scheduler on every tick (prefill_tick_share derives the same from outside)."""

UNIT = "slots"
SOURCE = "program_counter"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(run):
    counts = [s.attrs["prefill"] for s in run.spans
              if s.name == "engine/tick" and "prefill" in s.attrs]
    return sum(counts) / len(counts) if counts else None
