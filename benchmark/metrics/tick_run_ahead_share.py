"""Of the window's `engine/tick` spans, the share whose `ahead` is 1: the ticks
that were launched while the tick before them was still on the device, so the
chip went from one into the next without waiting for the host. A tick can be
launched ahead only behind a tick whose ids were left on the device, so this is
at most `tick_late_read_share`; the rest are the ticks behind a tick that was
read at once (a first token, the engine about to idle) and those whose launch
found the tick before already done. A program without the attr (it is new)
leaves the metric out."""

UNIT = "%"
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "tpot_p50_ms"


def read(run):
    ahead = [s.attrs["ahead"] for s in run.spans
             if s.name == "engine/tick" and "ahead" in s.attrs]
    return 100.0 * sum(ahead) / len(ahead) if ahead else None
