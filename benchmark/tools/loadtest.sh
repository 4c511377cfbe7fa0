#!/bin/bash
# One cell, one run per given number of CPU hogs (busy loops beside the
# benchmark), in one chip call: does a loaded host change the work?
#   chiprun -- bash benchmark/tools/loadtest.sh <cell> <seconds> <seed> <hogs>...
# The check's machine may share its host's cores; a run whose tokens or
# order of requests differ from the unloaded run's has a race in it.
cell=$1; seconds=$2; seed=$3; shift 3
for hogs in "$@"; do
  pids=()
  for _ in $(seq 1 "$hogs"); do
    sh -c 'while :; do :; done' & pids+=($!)
  done
  bash benchmark/tools/runset.sh "$cell" "$seconds" "hogs$hogs" 0 "$seed"
  [ ${#pids[@]} -gt 0 ] && kill "${pids[@]}" 2>/dev/null
  wait
done
