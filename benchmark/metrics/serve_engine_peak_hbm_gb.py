"""Peak device memory of the chip BEFORE the reference first ran, in 1e9
bytes: what the engine itself held at its fullest over set-up, window and
drain (weights, pools, feeds and its ticks' reserved scratch), as the adapter
noted it on the reference's first call (`peak_before_reference`). In a cell
whose reference runs on the chip beside the live engine, serve_peak_hbm_gb
reads the reference's blocks on top of this. An adapter that notes nothing
leaves the metric out."""

UNIT = "GB"
SOURCE = "program_counter"
LAYER = "device"
MOVES = "tpot_p50_ms"


def read(run):
    peak = getattr(run.cell.adapter, "peak_before_reference", None)
    return peak / 1e9 if peak else None
