#!/bin/bash
# PR 29, call c3 (one chip): chiprun --chips 1 --timeout 3000 -- bash benchmark/chip_calls/pr29_c3_steady_sets.sh
# two sets of 6 runs of the steady cell at 8 req/s (the same seeds in both), then its second traced run
out=chiprun_out/pr29/c3; mkdir -p $out
python3 benchmark/sets.py --workload qwen25-3b.chat-steady --seeds 51,52,53,54,2147483931,2147483933 --sets 2 --out $out > $out/table.txt 2>&1
python3 benchmark/run.py --workload qwen25-3b.chat-steady --seed 2147483935 --seconds 51 --trace 1 > $out/traced.out 2> $out/traced.err
echo "traced rc=$?"; tail -1 $out/traced.out | cut -c1-3000
grep -h "samples: token gaps" $out/*.out | cut -c1-260
grep -v "^set [12] seed" $out/table.txt | cut -c1-700 | tail -45
