"""Share of set-up's executables that came out of the persistent compile
cache: 100 x the sum of `cache_loads` over the sum of `executables`, both over
set-up's kept `compile` spans (`setup_trace_s.py`). 100 in a warm process since
the cache admits every executable whatever its compile time
(`paddle_tpu/core/compile_cache.py`); under that, what is missing compiled
anew (`setup_compile_s` has its seconds, the kept spans the `program`). A
program whose spans carry no `cache_loads` (the parent of PR 58) or that built
no executable during set-up has nothing to read."""

from .setup_trace_s import total

UNIT = "%"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(run):
    loads, built = total(run, "cache_loads"), total(run, "executables")
    if loads is None or not built:
        return None
    return 100.0 * loads / built
