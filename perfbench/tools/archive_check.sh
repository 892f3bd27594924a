#!/bin/bash
# Prove that the committed files are enough (chip only). Before the call:
#   git add -A && rm -rf _archive_check && mkdir _archive_check \
#     && git archive $(git write-tree) | tar -x -C _archive_check
#   chiprun -- bash perfbench/tools/archive_check.sh <cell> <seconds>
# Runs the cell twice from the unpacked tree with no cache directory given
# (so the checkout's own .jax_cache is used: the first run compiles, the
# second has to find every program), then once in a directory that holds
# only BENCHMARK.json and the benchmark's paths, which has to fail.
cell=$1; seconds=$2
cd _archive_check || exit 1
unset JAX_COMPILATION_CACHE_DIR
for n in 1 2; do
  python3 perfbench/run.py --workload "$cell" --seed $((2147483700 + n)) --seconds "$seconds" --trace 0 \
    > ../chiprun_out/archive.$n.out 2> ../chiprun_out/archive.$n.err
  echo "archive run $n rc=$? $(tail -n1 ../chiprun_out/archive.$n.out | cut -c1-600)"
  grep "^\[start\|^\[setup\|^\[warmup\|^\[done" ../chiprun_out/archive.$n.out | cut -c1-300
done
mkdir -p ../_archive_check_bare && cp -r BENCHMARK.json perfbench ../_archive_check_bare/ && cd ../_archive_check_bare || exit 1
python3 perfbench/run.py --workload "$cell" --seed 5 --seconds 5 --trace 0 > ../chiprun_out/bare.out 2> ../chiprun_out/bare.err
echo "bare run rc=$? (has to be non-zero) last stdout line: $(tail -n1 ../chiprun_out/bare.out | cut -c1-200)"
tail -n2 ../chiprun_out/bare.err | cut -c1-300
