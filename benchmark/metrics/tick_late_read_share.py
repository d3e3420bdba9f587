"""Of the window's `engine/tick` spans, the share whose `late` is 1: the ticks
whose ids stayed on the device, where the next tick's decode rows took them,
and were read and committed after that next launch, beside the device, instead
of in front of it. A tick is read at once (`late` 0) where a first token comes
out of it or no tick is certain to follow. A program without the attr (it is
new) leaves the metric out."""

UNIT = "%"
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "tpot_p50_ms"


def read(run):
    late = [s.attrs["late"] for s in run.spans
            if s.name == "engine/tick" and "late" in s.attrs]
    return 100.0 * sum(late) / len(late) if late else None
