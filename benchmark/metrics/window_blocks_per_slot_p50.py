"""Median over the run's commits of the window-pool blocks a request held
right after the commit: the pager counts them where it releases what slid out
of the window (`stats()["pager"]["window"]["blocks_held"]`, a histogram: index
= blocks, value = commits; set-up's commits are in it, a thousandth of the
window's). It guards the release behind the window: about 3 with it (a window
of 128 positions over blocks of 64), 260 without, when the window layers'
reads would also span every block again. The adapter keeps the engine it
built (`last_engine`); a program without the counter, or an adapter without
the engine, leaves the metric out."""

import numpy as np

UNIT = "blocks"
SOURCE = "program_counter"
LAYER = "pager"
MOVES = "tpot_p50_ms"


def window_stats(run):
    """`stats()["pager"]["window"]` of the engine the adapter built; None
    where there is no such engine or it has no window pool."""
    engine = getattr(run.cell.adapter, "last_engine", None)
    if engine is None:
        return None
    return (engine.stats().get("pager") or {}).get("window")


def read(run):
    window = window_stats(run)
    if not window or not sum(window["blocks_held"]):
        return None
    held = np.asarray(window["blocks_held"], np.float64)
    at = np.searchsorted(np.cumsum(held), held.sum() / 2.0)
    return float(at)
