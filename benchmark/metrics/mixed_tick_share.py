"""The share of the window's `engine/tick` span time spent in ticks that
carried a prefill lane (`prefill` > 0): how much of the time a decoding user's
token gap is set by other users' prompts. A cell of long unshared prompts is
what its name says only while this stays high. A program without the attr
leaves the metric out."""

UNIT = "%"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"


def read(run):
    ticks = [s for s in run.spans
             if s.name == "engine/tick" and "prefill" in s.attrs]
    total = sum(s.duration_ms for s in ticks)
    if not total:
        return None
    return 100.0 * sum(s.duration_ms for s in ticks
                       if s.attrs["prefill"] > 0) / total
