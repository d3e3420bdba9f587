"""The sparse latent read's share of its roofline over the ticks that ran
under the profiler: the sum over those ticks of the read's least time (the
adapter's `dsa_call` at the tick's `dsa_rows`, `dsa_live_positions` and
`dsa_selected_positions`: every live pooled key scored once, every selected
row of c read ONCE) over the device seconds the read took inside those ticks'
programs: what ran under the scopes `dsa_index` (the pooled row's write, the
index scores, the top-k) and `sparse_latent_attention` (the gather of the
picked rows into the scratch) by `benchmark/scopes.py`, plus the Mosaic call
that attends the scratch (the latent read's decode body, found by its name:
this model has no dense latent read). The scratch is written and read back,
which the least time does not count: the share says what a read that fetched
the picked rows straight from the pool could save. Counts and seconds come
from the SAME ticks and are SUMMED (`kda_decode_roofline`'s rule). A trace
without the scopes, a program without the counters, or an adapter without the
counts, leaves the metric out."""

from .. import scopes
from ..counts import roofline_min_seconds

UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p50_ms"

KERNEL = "latent_paged_attention_custom-call_"
ATTRS = ("dsa_rows", "dsa_live_positions", "dsa_selected_positions")


def read(run):
    call = getattr(run.cell.adapter, "dsa_call", None)
    found = getattr(run.trace, "scope_ops", None)
    if call is None or found is None or not run.trace.devices:
        return None
    events = found.get("dsa_index", []) + found.get(
        "sparse_latent_attention", [])
    if not events:
        return None
    events += [(s, e) for s, e, key, _, mosaic in run.trace.devices[0].ops
               if mosaic and key.startswith(KERNEL)]
    inside = scopes.executions(run.trace)
    pairs = scopes.counted_pairs(
        run, inside, scopes.seconds_inside(inside, events), ATTRS)
    seconds = sum(t for _, t in pairs)
    if not seconds:
        return None
    least = sum(roofline_min_seconds(
        *call(run.cell.config, *(s.attrs[a] for a in ATTRS)),
        run.device["peaks"]) for s, _ in pairs)
    return 100.0 * least / seconds
