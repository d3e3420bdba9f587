#!/bin/sh
# sh benchmark/runs.sh <log> <cell> <trace> <seed> [<seed> ...]
# A builder's tool: one run of the cell a seed at the manifest's run_seconds, each
# run's standard output appended to <log> (benchmark/spread.py reads a log as one
# set) and its standard error to <log>.err. Prints how many runs were correct.
log=$1; cell=$2; trace=$3; shift 3
seconds=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
mkdir -p "$(dirname "$log")"
for seed in "$@"; do
  echo "## $cell seed $seed trace $trace" >> "$log"
  python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" >> "$log" 2>> "$log.err"
  echo "## exit $?" >> "$log"
done
grep -c '^{"correct": true' "$log"
