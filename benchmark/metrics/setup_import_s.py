"""Seconds the program's own import took: the `paddle_tpu/import` span, from
the package's first line to its last (JAX's import is inside it where nothing
imported JAX before; `setup_parts`' `import` is the benchmark's clock around
all imports and the manifest)."""

from .setup_trace_s import setup_spans

UNIT = "s"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    took = [s.end - s.start for s in setup_spans(run) or ()
            if s.name == "paddle_tpu/import"]
    return sum(took) if took else None
