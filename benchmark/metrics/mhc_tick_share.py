"""The stream mixing's share of the tick programs' device time over the
ticks that ran under the profiler: the device seconds of the operations
traced under the scope `hyper_connection` (the three maps and Sinkhorn, the
mix into a sub-layer's row and back into the four streams: XLA's fusions,
found by their `op_name`: benchmark/scopes.py) over the seconds in which the
tick programs' executions were busy. What four residual streams cost a tick
over one. A trace without the scopes leaves the metric out."""

from .. import scopes

UNIT = "%"
SOURCE = "device_trace"
LAYER = "model step"
MOVES = "tpot_p50_ms"


def read(run):
    found = getattr(run.trace, "scope_ops", None)
    if found is None or not found.get("hyper_connection") \
            or not run.trace.devices:
        return None
    inside = scopes.executions(run.trace)
    busy = scopes.seconds_inside(inside, run.trace.devices[0].busy())
    if not sum(busy):
        return None
    return 100.0 * sum(scopes.seconds_inside(
        inside, found["hyper_connection"])) / sum(busy)
