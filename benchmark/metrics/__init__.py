"""One reader per metric, found by the metric's name in BENCHMARK.json. A reader
states UNIT and SOURCE (and, for a per-layer metric, LAYER and MOVES) as the
manifest has them, and read(run) returns the value or None when the run has
nothing for it to read."""
