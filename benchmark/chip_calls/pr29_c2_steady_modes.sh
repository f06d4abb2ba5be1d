#!/bin/bash
# PR 29, call c2 (one chip): chiprun --chips 1 --timeout 1800 -- bash benchmark/chip_calls/pr29_c2_steady_modes.sh
# the steady cell at 0.8 x the new knee (8 req/s): four runs to read the gap ladders and each mode's share
# of the gaps (which percentile lies inside a mode in every run), then a traced run of each cell
out=chiprun_out/pr29/c2; mkdir -p $out
python3 benchmark/sets.py --workload qwen25-3b.chat-steady --seeds 2147483921,2147483923,31,32 --sets 1 --out $out > $out/table.txt 2>&1
grep -h "samples: token gaps\|gap mode" $out/*.out | cut -c1-260
grep -v "^set 1 seed" $out/table.txt | cut -c1-400
for name in chat-steady decode-saturated; do
  python3 benchmark/run.py --workload qwen25-3b.$name --seed 2147483925 --seconds 51 --trace 1 > $out/traced_$name.out 2> $out/traced_$name.err
  echo "traced $name rc=$?"; tail -1 $out/traced_$name.out | cut -c1-2500; grep "window \|correctness\|comparison\|profiler\|trace reduced" $out/traced_$name.out | cut -c1-400; tail -3 $out/traced_$name.err | cut -c1-200
done
