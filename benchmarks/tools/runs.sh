#!/bin/bash
# Several runs of one cell in one call, each with its own seed: the sets
# that the bounds are set from. Usage:
#   bash benchmarks/tools/runs.sh <workload> <seconds> <trace> <tag> <seed>...
# Keeps each run's log under chiprun_out/<tag>_<seed>.log and prints the
# counts, the failed checks and the result line of each.
w=$1; s=$2; t=$3; tag=$4; shift 4
mkdir -p chiprun_out
for seed in "$@"; do
  log=chiprun_out/${tag}_${seed}.log
  python3 -m benchmarks.run --workload $w --seed $seed --seconds $s --trace $t > $log 2>&1
  echo "== $w seed=$seed rc=$? $(grep -c FAIL $log) failed checks"
  grep "^counts:\|FAIL\|^setup_s\|^warm-up\|Error" $log | cut -c1-900
  tail -1 $log | cut -c1-2500
done
