"""The delta-rule decode update's share of its roofline over the ticks that
ran under the profiler: the sum over those ticks of the update's least time
(the larger of bytes over bandwidth and operations over peak; the live rows'
state S read AND written, a float32 matrix of heads x head_dim x head_dim a
kda layer, and the rows' q, k, beta k, decays and v in and o out: the
adapter's `kda_decode_call` at the tick's `state_rows`), over the device
seconds the kernel's calls took inside those ticks' programs (one call a kda
layer, in the decode tick and in the mixed tick alike; found by its name and
its first result's shape: benchmark/kernel_ops.py). The kernel's time moves
with the live rows, row for row, so counts and seconds come from the SAME
ticks and are SUMMED, not a ratio of two medians: the trace holds N executions
of the tick programs, and the counts are those of the LAST N `engine/tick`
spans of the program's ring (`hybrid_tick_roofline`'s rule; the traced phase
ends the run). A program without the kernel, a traced tick without the attr
(its seconds would have to be guessed), or an adapter without the counts,
leaves the metric out."""

from .. import kernel_ops
from ..counts import roofline_min_seconds
from .hybrid_tick_roofline import tick_modules, traced_ticks

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    cell, cfg = run.cell, run.cell.config
    call = getattr(cell.adapter, "kda_decode_call", None)
    if call is None or run.trace is None or not run.trace.devices:
        return None
    key = kernel_ops.kernel_key(
        "kda_decode", "float32",
        (cell.spec["engine"]["n_slots"], cfg["num_attention_heads"],
         cfg["head_dim"]))
    modules = tick_modules(run.trace)
    spent = sum(t for name in modules for t, _ in
                kernel_ops.per_execution_seconds(run.trace, key, name))
    n = sum(len(run.trace.module_busy_seconds(name)) for name in modules)
    ticks = traced_ticks(run, n)
    if not spent or not ticks \
            or not all("state_rows" in s.attrs for s in ticks):
        return None
    least = sum(roofline_min_seconds(*call(cfg, s.attrs["state_rows"]),
                                     run.device["peaks"]) for s in ticks)
    # the ring may hold fewer spans than the trace has executions: the
    # seconds are then those of as many ticks as were counted
    return 100.0 * least / (spent * len(ticks) / n)
