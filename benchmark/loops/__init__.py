"""One loop per way of driving the system: `train` feeds an executor step
after step, `serve` offers an engine requests on a schedule. A cell's file
names its loop; each exposes run(cell, args, t_process_start) -> harness.Run.
"""
