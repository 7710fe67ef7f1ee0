#!/bin/bash
# Several runs of one cell, one after another, in one call on the chip:
#   benchmarks/tools/runs.sh <cell> <seconds> <trace> <label> <seed> [<seed> ...]
# Each run's checks, compile line and result line go to
# chiprun_out/<cell>.<label>.log (and the result lines to stdout).
cell=$1; seconds=$2; trace=$3; label=$4; shift 4
mkdir -p chiprun_out
out=chiprun_out/$cell.$label.log
for seed in "$@"; do
  echo "== $cell seed $seed seconds $seconds trace $trace" >> "$out"
  began=$(date +%s%N)
  python3 benchmarks/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" 2>/dev/null \
    | grep -E '^(check|compile|notes|set-up|loss path|(mlp|gnn|gru) |reference|trace:|\{)' | cut -c1-6000 >> "$out"
  echo "rc=${PIPESTATUS[0]} process_wall_ms=$(( ($(date +%s%N) - began) / 1000000 ))" >> "$out"
  tail -2 "$out" | head -1 | cut -c1-700
done
