"""Mean over the program's `engine/tick` spans of their `prefill` count, which
the scheduler takes on every tick. Since the engine chunks prompts (PR 29) it
is the prefill LANES the tick filled (0, 1 or 2 of the mixed tick's two
128-token lanes), so the mean is lanes filled a tick; under a program that
feeds one prompt token a tick it was the slots doing so. The name stays for
the ledger's history."""

UNIT = "slots"
SOURCE = "program_counter"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(run):
    counts = [s.attrs["prefill"] for s in run.spans
              if s.name == "engine/tick" and "prefill" in s.attrs]
    return sum(counts) / len(counts) if counts else None
