#!/bin/bash
# PR 24, second session, call 3 (one chip): chiprun --chips 1 --timeout 2100 -- bash benchmark/chip_calls/pr24_r3_steady_sets.sh
# two sets of 6 runs of the steady cell (the same seeds in both), then its traced run
python3 benchmark/sets.py --workload qwen25-3b.chat-steady --seeds 21,22,23,24,25,2147483661 --sets 2 --out chiprun_out/r3 > chiprun_out_r3_table.txt 2>&1
mkdir -p chiprun_out/r3; mv chiprun_out_r3_table.txt chiprun_out/r3/table.txt
python3 benchmark/run.py --workload qwen25-3b.chat-steady --seed 26 --seconds 51 --trace 1 > chiprun_out/r3/traced.out 2> chiprun_out/r3/traced.err
echo "traced rc=$?"; tail -1 chiprun_out/r3/traced.out | cut -c1-3000
grep -v "^set [12] seed" chiprun_out/r3/table.txt | cut -c1-900 | tail -45
