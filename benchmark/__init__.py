"""The repo's benchmark: the yardstick later PRs are held to (see PERF.md).

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the reduction from spans, counters and
the profiler's trace to metrics, the table of peaks, the operation and byte
counts, each configuration's plain reference and the comparison that decides
`correct`. From `paddle_tpu` the benchmark takes only the system under test
and its spans, counters and kernel names.
"""
