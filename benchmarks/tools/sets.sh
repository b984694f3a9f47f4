#!/bin/bash
# One or two sets of runs of a cell, one log per run under chiprun_out/.
# usage: sets.sh <workload> <seconds> <tag> <sets: "A B" or "A"> seeds...
W=$1; S=$2; TAG=$3; SETS=$4; shift 4
mkdir -p chiprun_out
for setn in $SETS; do for seed in "$@"; do
  log=chiprun_out/${TAG}_${setn}_${seed}.log
  python3 benchmarks/run.py --workload $W --seed $seed --seconds $S --trace 0 > $log 2>&1
  echo "set=$setn seed=$seed rc=$? checks_ok=$(grep -h '"check"' $log | grep -c '"ok": true') $(tail -n 1 $log | cut -c1-600)"
done; done
