#!/bin/bash
# One set of runs of one cell, in one chip call: each run another seed.
#   chiprun -- bash benchmark/tools/runset.sh <cell> <seconds> <tag> <trace> <seed>...
# Full logs go to chiprun_out/sets/<cell>/<tag>.<seed>.t<trace>.log; the
# result lines (and the set-up split) are echoed.
cell=$1; seconds=$2; tag=$3; trace=$4; shift 4
out=chiprun_out/sets/$cell; mkdir -p "$out"
for seed in "$@"; do
  log=$out/$tag.$seed.t$trace.log
  python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" > "$log" 2>&1
  echo "== $cell seed $seed trace $trace rc=$?"
  grep -a "^\[bench" "$log" | grep -av "series " | cut -c1-700 | tail -12
  grep -a "series " "$log" | cut -c1-200
  tail -n 1 "$log" | cut -c1-3000
done
