"""The share of the positions its rows HOLD that the sparse latent read
attends, over the window's ticks: the sum of `engine/tick`'s
`dsa_selected_positions` over the sum of `dsa_live_positions` (both summed
over the tick's decode and lane rows; the selection is the best 512 groups of
4 and the tail). ~6% at 33k positions; near 100% means the traffic stopped
exercising the selection (contexts under 2,048 positions are read densely).
A program without the counters leaves the metric out."""

UNIT = "%"
SOURCE = "program_counter"
LAYER = "kernels"
MOVES = "tpot_p50_ms"


def read(run):
    ticks = [s.attrs for s in run.spans if s.name == "engine/tick"
             and "dsa_live_positions" in s.attrs
             and "dsa_selected_positions" in s.attrs]
    live = sum(a["dsa_live_positions"] for a in ticks)
    if not live:
        return None
    return 100.0 * sum(a["dsa_selected_positions"] for a in ticks) / live
