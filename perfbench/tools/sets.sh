#!/bin/bash
# Two sets of runs of one cell with the same seeds, as a bound is set from
# (chip only):  chiprun -- bash perfbench/tools/sets.sh <cell> <seconds> <seed>...
# Appends "SET <A|B> seed=<n> rc=<rc> <result line>" to
# chiprun_out/sets.<cell>.txt (read it with tools/spread.py) and echoes each
# run's observations.
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out
for rep in A B; do
  for s in "$@"; do
    python3 perfbench/run.py --workload "$cell" --seed "$s" --seconds "$seconds" --trace 0 \
      > chiprun_out/last.out 2> chiprun_out/last.err
    rc=$?
    echo "SET $rep seed=$s rc=$rc $(tail -n1 chiprun_out/last.out)" | tee -a "chiprun_out/sets.$cell.txt" | cut -c1-900
    grep "^\[model\|^\[warmup\|^\[window\|^\[check\|^\[done" chiprun_out/last.out | cut -c1-330
    grep "^perfbench" chiprun_out/last.err | cut -c1-300
  done
done
