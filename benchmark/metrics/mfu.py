"""Model FLOP/s utilisation: the operations forward and backward need for the
tokens counted (from shapes, benchmark/counts.py; recomputation not counted),
per second of the window, from its first step's dispatch to its last one's
loss on the host (the steps overlap: the loop keeps some in flight), over
chips x the table's bf16 peak."""

UNIT = "%"
SOURCE = "host_clock"
LAYER = "model step"
MOVES = "train_tokens_per_s"


def read(run):
    if not run.steps:
        return None
    cell = run.cell
    # the ring is fed round-robin, so over whole rings the mean per step is
    # exact; the window's last, partial ring is taken at that mean
    per_step = sum(cell.adapter.train_flops(cell.config, cell.traffic, b)
                   for b in run.batches) / len(run.batches)
    flops = per_step * len(run.steps)
    elapsed = run.steps[-1][1] - run.steps[0][0]
    peak = run.device["peaks"]["bf16_flops_per_s"] * run.device["count"]
    return 100.0 * flops / elapsed / peak
