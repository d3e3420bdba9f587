"""What the program sets up: process start to the window's opening (imports,
building the graph and the traffic, the weights on the device from the seed,
compile or load from the cache, warming the cell's shapes) LESS the TPU
runtime's start, which `harness.start_run` times around the first
touch of the device and the line before the last shows as `setup_parts`'
`runtime_start`. That start read 6 to 22 s from run to run of one tree on one
machine (PERF.md, section 6) and decided a run's total; nothing a PR can
change is in it. A change that moves work INTO the first touch of the device
would hide it here: `runtime_start` is printed beside every run for that."""

UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
