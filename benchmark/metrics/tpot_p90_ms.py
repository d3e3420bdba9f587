"""90th percentile over requests of (done - first token) / (output tokens - 1)."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "host_clock"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"


def read(run):
    ok = [r for r in run.requests if r["ok"] and r["n_out"] > 1]
    if not ok or any(not r["ok"] for r in run.requests):
        return None
    return 1e3 * quantile([(r["done"] - r["first"]) / (r["n_out"] - 1)
                           for r in ok], 0.9)
