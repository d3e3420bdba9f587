"""Of the picks a tick's live rows made (decode rows and lane tokens,
`state_rows` + `prefill_tokens` on `engine/tick`, times the configuration's
top-k and its routed layers), the share that fell on the experts this chip
holds (`routed_rows`, the device's own count, read with the ids), median a
tick of the window. The group step decides which CHIPS a row visits: a rank
that holds two groups of eight reads 25 where the seeded bias and the group
step send it its quarter; more says the router leans on this rank and its
tick streams more experts than its share (lower is better, as
`routed_pairs_held_share` has it for the training routers). A program
without the attrs, or a configuration without a group step, leaves the
metric out."""

from ..harness import quantile

UNIT = "%"
SOURCE = "program_counter"
LAYER = "router"
MOVES = "tpot_p50_ms"


def read(run):
    cfg = run.cell.config
    n_moe = getattr(run.cell.adapter, "n_moe", None)
    if n_moe is None or "n_group" not in cfg:
        return None
    per_row = cfg["num_experts_per_tok"] * n_moe(cfg)
    shares = []
    for s in run.spans:
        if s.name != "engine/tick" or not {"routed_rows", "state_rows"} \
                <= set(s.attrs):
            continue
        rows = s.attrs["state_rows"] + s.attrs.get("prefill_tokens", 0)
        if rows:
            shares.append(100.0 * s.attrs["routed_rows"] / (rows * per_row))
    return quantile(shares, 0.5)
