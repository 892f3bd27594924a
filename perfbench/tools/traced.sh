#!/bin/bash
# Traced runs of one cell, one per seed (chip only):
#   chiprun -- bash perfbench/tools/traced.sh <cell> <seconds> <seed>...
# Echoes each run's result line and observations.
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out
for s in "$@"; do
  python3 perfbench/run.py --workload "$cell" --seed "$s" --seconds "$seconds" --trace 1 \
    > chiprun_out/last.out 2> chiprun_out/last.err
  echo "TRACED seed=$s rc=$? $(tail -n1 chiprun_out/last.out)" | tee -a "chiprun_out/traced.$cell.txt" | cut -c1-3000
  grep "^\[model\|^\[warmup\|^\[window\|^\[check\|^\[trace\|^\[done" chiprun_out/last.out | cut -c1-400
  grep "^perfbench" chiprun_out/last.err | cut -c1-300
done
