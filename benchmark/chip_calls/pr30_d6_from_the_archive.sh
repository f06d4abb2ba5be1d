#!/bin/bash
# PR 30, call d6 (one chip): chiprun --chips 1 --timeout 1500 -- bash benchmark/chip_calls/pr30_d6_from_the_archive.sh [first seed] [tag]
# the committed files alone (`git archive $(git write-tree)` unpacked into _checkout/final), a fresh seed:
# the benchmark's own command in the new cell, --trace 0 and --trace 1
out=$PWD/chiprun_out/pr30/${2:-d6}; mkdir -p $out
cd _checkout/final || exit 9
for trace in 0 1; do
  python3 benchmark/run.py --workload pangu-ultra-moe-ep16.reason-saturated --seed $((${1:-2147483861} + trace)) --seconds 51 --trace $trace \
    > $out/run_t$trace.out 2> $out/run_t$trace.err
  echo "run.py trace $trace rc=$? $(tail -1 $out/run_t$trace.out | cut -c1-3500)"
  grep "window \|correctness:\|comparison\|gap mode" $out/run_t$trace.out | cut -c1-330
done
