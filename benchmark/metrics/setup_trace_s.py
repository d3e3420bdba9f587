"""Seconds of set-up that JAX reports as tracing: the sum of `trace_s` over
the program's kept `compile` spans that ended before the window opened: each
`executor/compile_or_load` span (a program's first run: its outermost trace,
the nested ones inside it) and the `jax/unscoped` records (traces outside any
such span, a weight builder's jitted helpers, each counted whole).

The other `setup_*` readers take `setup_spans` and `total` from here. The
spans are the program's (`tracing.compile_spans()`, kept past the ring, on
`perf_counter` like `run.t0`); a program without them (or with PTPU_TRACE=0)
has nothing to read."""

UNIT = "s"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def window_opening(run):
    """When the window opened, on the spans' clock (`Run.open_window`)."""
    return run.t0 + run.setup_s + run.setup_parts["runtime_start"]


def setup_spans(run):
    """The kept `compile` spans of set-up, or None where there are none."""
    from paddle_tpu.observability import tracing
    kept = getattr(tracing, "compile_spans", None)
    if kept is None or run.setup_s is None:
        return None
    t_open = window_opening(run)
    return [s for s in kept() if s.end <= t_open] or None


def total(run, attr):
    """Sum of `attr` over set-up's spans that carry it."""
    values = [s.attrs[attr] for s in setup_spans(run) or ()
              if attr in s.attrs]
    return sum(values) if values else None


def read(run):
    return total(run, "trace_s")
