"""Of the ticks the window's requests took to their first token, the share in
which the request was in prefill and got no lane: sum of `lane_wait_ticks`
over sum of `ticks` of the `request/prefill` spans
(`GenRequest.lane_wait_ticks`, counted in `_fill_lanes` over the slots in
prefill beyond the lanes)."""

UNIT = "%"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "ttft_p90_ms"


def read(run):
    spans = [s.attrs for s in run.spans
             if s.name == "request/prefill" and "ticks" in s.attrs]
    ticks = sum(a["ticks"] for a in spans)
    return (100.0 * sum(a["lane_wait_ticks"] for a in spans) / ticks
            if ticks else None)
