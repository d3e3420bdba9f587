"""99th percentile of (submit time - due time): how late the benchmark's own
generator ran. Arrivals are polled between ticks, so up to a tick is expected."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "host_clock"
LAYER = "load generator"
MOVES = "ttft_p90_ms"


def read(run):
    late = [r["submitted"] - r["due"] for r in run.requests if "submitted" in r]
    return 1e3 * quantile(late, 0.99) if late else None
