"""Of the window's `engine/copy_back` spans, the share whose `found` is 1: the
reads of a tick's ids that found them on the host already, because their copy
was enqueued with the tick's launch and had arrived by the time the host came
to read (behind the next launch on a tick read late, behind the wait on a
sampled eager one). "Found" is the read's own duration: `np.asarray` returned
within 0.1 ms (`_TickPacer.FOUND_WITHIN_S`; a read that finds the bytes takes
0.01-0.04 ms on a TPU's host, one that waits for the copy 0.2 ms and more).
The rest waited for the copy on the thread that makes the next launch:
`tick_copy_back_ms_p50` says how long. A program without the attr (it is new)
leaves the metric out."""

UNIT = "%"
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "tpot_p50_ms"


def read(run):
    found = [s.attrs["found"] for s in run.spans
             if s.name == "engine/copy_back" and "found" in s.attrs]
    return 100.0 * sum(found) / len(found) if found else None
