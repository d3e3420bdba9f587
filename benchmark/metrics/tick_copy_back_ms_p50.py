"""Median of the program's `engine/copy_back` spans: on the ticks that realize
their ids in two parts (one in sixteen), inside `engine/wait`, after the
device's last op is known done, the time until the sampled ids are a host
array (`np.asarray` of a fetch whose copy was enqueued before the wait). The
chip stands idle through it: the device trace's idle gaps under
`engine/copy_back` and jax's own `np.asarray` annotation inside it are the
same time on the device's clock."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "tpot_p50_ms"


def read(run):
    return quantile(run.span_ms("engine/copy_back"), 0.5)
