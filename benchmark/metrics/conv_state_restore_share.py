"""Of the window's requests that started from a span of the prefix cache
(`shared_len` > 0 in the loop's record of the request), the share whose conv
state came from a block's snapshot: `state_restored`, counted by the pager
where it hands the span out and carried by the `engine/admit` spans. 100, or
a request resumed a shared span from a state nobody computed. (The pager
offers a block to the index only with its snapshot and raises on an indexed
block without one, so a run that reads less has failed requests too.) It
moves `tpot_p50_ms` in the one cell that lists it: a request without its span
prefills its 1,024-token preamble itself, eight more mixed ticks that every
live slot's decode waits through (the cell reports no TTFT metric: PERF.md
section 6, PR 39). A program without the attr leaves the metric out."""

UNIT = "%"
SOURCE = "program_counter"
LAYER = "pager"
MOVES = "tpot_p50_ms"


def read(run):
    admits = [s for s in run.spans if s.name == "engine/admit"
              and "state_restored" in s.attrs]
    shared = sum(1 for r in run.requests if r.get("shared_len"))
    if not admits or not shared:
        return None
    return 100.0 * sum(s.attrs["state_restored"] for s in admits) / shared
