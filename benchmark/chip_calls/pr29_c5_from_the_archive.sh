#!/bin/bash
# PR 29, call c5 (one chip): chiprun --chips 1 --timeout 1500 -- bash benchmark/chip_calls/pr29_c5_from_the_archive.sh
# the committed files alone (`git archive $(git write-tree)` unpacked into _checkout/final), fresh seeds:
# the benchmark's own command in each cell with --trace 0, and in the steady cell with --trace 1
out=$PWD/chiprun_out/pr29/c5; mkdir -p $out
cd _checkout/final || exit 9
for cell in decode-saturated:2147483951:0 chat-steady:2147483953:0 chat-steady:71:1; do
  IFS=: read name seed trace <<< "$cell"
  python3 benchmark/run.py --workload qwen25-3b.$name --seed $seed --seconds 51 --trace $trace > $out/run_${name}_t$trace.out 2> $out/run_${name}_t$trace.err
  echo "run.py $name trace $trace rc=$? $(tail -1 $out/run_${name}_t$trace.out | cut -c1-1500)"
  grep "gap mode cte2048\|correctness:\|comparison" $out/run_${name}_t$trace.out | cut -c1-330
  tail -11 $out/run_${name}_t$trace.err | cut -c1-120 | tr '\n' ';'; echo
done
