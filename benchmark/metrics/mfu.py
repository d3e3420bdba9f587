"""Model FLOP/s utilisation: the operations forward and backward need for the
tokens counted (from shapes, benchmark/counts.py; recomputation not counted),
per second the steps themselves took (the profiler's start and stop, which a
traced run has between two steps, are not step time), over chips x the table's
bf16 peak."""

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
    elapsed = sum(end - start for start, end, _ in run.steps)
    peak = run.device["peaks"]["bf16_flops_per_s"] * run.device["count"]
    return 100.0 * flops / elapsed / peak
