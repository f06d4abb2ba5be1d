#!/bin/bash
# PR 24, second session, call 2 (one chip): chiprun --chips 1 --timeout 1800 -- bash benchmark/chip_calls/pr24_r2_saturated_sets.sh
# two sets of 6 runs of the saturated cell (the same seeds in both), then its traced run
python3 benchmark/sets.py --workload qwen25-3b.decode-saturated --seeds 11,12,13,14,15,2147483659 --sets 2 --out chiprun_out/r2 > chiprun_out_r2_table.txt 2>&1
mkdir -p chiprun_out/r2; mv chiprun_out_r2_table.txt chiprun_out/r2/table.txt
python3 benchmark/run.py --workload qwen25-3b.decode-saturated --seed 16 --seconds 51 --trace 1 > chiprun_out/r2/traced.out 2> chiprun_out/r2/traced.err
echo "traced rc=$?"; tail -1 chiprun_out/r2/traced.out | cut -c1-3000
grep -v "^set [12] seed" chiprun_out/r2/table.txt | cut -c1-900 | tail -45
