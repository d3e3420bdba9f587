"""90th percentile of the time from when a request was DUE to its first sampled
token on the host. With some 130 requests a dozen lie beyond it; a 95th would
have half as many."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    ok = [r for r in run.requests if r["ok"]]
    if not ok or len(ok) < len(run.requests):
        return None        # a failed request misses every percentile
    return 1e3 * quantile([r["first"] - r["due"] for r in ok], 0.9)
