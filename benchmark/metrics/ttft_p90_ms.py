"""90th percentile of the time from when a request was DUE to its first sampled
token on the host. The mix's rate decides how many requests lie beyond it: a
tenth of round(rate_per_s x --seconds); PERF.md section 2 has the count."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    ok = [r for r in run.requests if r["ok"]]
    if not ok or len(ok) < len(run.requests):
        return None        # a failed request misses every percentile
    return 1e3 * quantile([r["first"] - r["due"] for r in ok], 0.9)
