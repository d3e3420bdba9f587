"""Of the admissions that matched a span of the prefix index, the share that
was handed the WHOLE span: every window-pool block its first chunk reads (the
span's window tail) was still resident. The pager counts both where it admits
(`stats()["pager"]["window"]`: `tail_hits` over `tail_lookups`, set-up's
admissions among them; none of those matches anything). A hit that is cut
prefills its session's 16k tokens again through the lanes, 128 ticks that
every live row's decode waits through. The adapter keeps the engine it built
(`last_engine`); a program without the counters leaves the metric out."""

from .window_blocks_per_slot_p50 import window_stats

UNIT = "%"
SOURCE = "program_counter"
LAYER = "pager"
MOVES = "tpot_p50_ms"


def read(run):
    window = window_stats(run)
    if not window or not window["tail_lookups"]:
        return None
    return 100.0 * window["tail_hits"] / window["tail_lookups"]
