"""Time the machine took from the loop's thread over the window and its
drain: wall clock, less the thread's own user and system time
(`getrusage(RUSAGE_THREAD)`), less the program's `engine/wait` spans, in which
the thread sleeps on the device. The loop spins where it has nothing to tick,
so what is left is time in which the thread wanted to run and did not: the
machine's host ran something else on its vCPU (PERF.md, section 6, PR 31:
0.06 to 6.7 s of 45, and the tick slows with it).

It has an offset: the thread also burns CPU inside `engine/wait` (the copy
back and the wake-up, 0.06 to 0.16 ms a tick), which is subtracted twice, so
at 13,000 ticks a window it reads -0.8 to -2.1 s (PERF.md, section 6, PR 35).
Compare it between runs of one rate and one program, never with zero. A
report beside a run's latencies, never a filter: no run is dropped or
reweighted by it."""

UNIT = "ms"
SOURCE = "host_clock"
LAYER = "load generator"
MOVES = "ttft_p90_ms"


def read(run):
    clock = run.window_clock
    waits = run.span_ms("engine/wait")
    if not clock or not waits:
        return None
    return 1e3 * (clock["wall_s"] - clock["thread_cpu_s"]) - sum(waits)
