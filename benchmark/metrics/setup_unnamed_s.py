"""`setup_s` less the union of set-up's kept `compile` spans on the main thread
(the import, every `executor/build_step`, every `executor/compile_or_load`):
what set-up spent under no span of the program. Weights to the device, the
traffic, the warm steps or ticks, the benchmark's and JAX's own imports are in
it, and so is whatever the `jax/unscoped` records hold (sums with a kind and
no place; their interval is not one the process spent compiling). It is
`step_untraced_ms_p50`'s twin: it keeps the other `setup_*` honest. Never
below zero."""

import threading

from .setup_trace_s import setup_spans, window_opening

UNIT = "s"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    spans = setup_spans(run)
    if spans is None:
        return None
    main = threading.main_thread().ident
    lo, hi = run.t0, window_opening(run)
    covered, reach = 0.0, lo
    for a, b in sorted((s.start, s.end) for s in spans
                       if s.thread_id == main and s.name != "jax/unscoped"):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            covered += b - a
            reach = b
    return max(run.setup_s - covered, 0.0)
