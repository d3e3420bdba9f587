"""The WHOLE tick's share of its roofline, decode ticks and mixed ticks alike,
over the ticks that ran under the profiler: the sum over those ticks of each
tick's least time (the adapter's `tick_call`: every byte the tick must stream,
the dense weights once, the touched experts, every live decode row's state
read and written, the pooled keys scored and the selected rows of c, the
head's slice; or its operations over the peak where that is more) over the
device seconds those ticks' programs were busy. Named with `mfu`: it is the
cell's share of the whole step's peak. Counts and seconds come from the SAME
ticks (`hybrid_tick_roofline`'s rule); a tick whose counts never came back
(the routed counts ride on the NEXT tick's read: the phase's last tick has
none) is left out WITH its seconds. A program without the counters, or an
adapter without the counts, leaves the metric out."""

from .. import scopes
from ..counts import roofline_min_seconds

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"

ATTRS = ("state_rows", "experts_touched", "routed_rows",
         "dsa_live_positions", "dsa_selected_positions")


def read(run):
    call = getattr(run.cell.adapter, "tick_call", None)
    if call is None or run.trace is None or not run.trace.devices:
        return None
    inside = scopes.executions(run.trace)
    busy = scopes.seconds_inside(inside, run.trace.devices[0].busy())
    pairs = scopes.counted_pairs(run, inside, busy, ATTRS)
    seconds = sum(t for _, t in pairs)
    if not seconds:
        return None
    least = sum(roofline_min_seconds(
        *call(run.cell.config,
              s.attrs["state_rows"] + s.attrs.get("prefill_tokens", 0),
              *(s.attrs[a] for a in ATTRS)), run.device["peaks"])
        for s, _ in pairs)
    return 100.0 * least / seconds
