"""Kernel bodies set-up traced: the sum of `kernel_bodies_traced` over the
`executor/compile_or_load` spans of set-up (`setup_trace_s.py`), the
`flash/body_traced`, `moe_train/body_traced` and `ssm/body_traced` samples
recorded inside them. A body a kernel a step, not a body a layer (PERF.md,
section 6, PR 47): a program that traces a body at every call site again
shows here, and in `setup_trace_s`."""

from .setup_trace_s import total

UNIT = "bodies"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    return total(run, "kernel_bodies_traced")
