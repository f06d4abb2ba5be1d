#!/bin/bash
# PR 30, call d4 (one chip): chiprun --chips 1 --timeout 3300 -- bash benchmark/chip_calls/pr30_d4_set.sh
# the new cell: one set of 6 untraced runs at the
# manifest's run_seconds, each on a seed of its own (the spreads against half the bounds)
out=chiprun_out/pr30/d4; mkdir -p $out
cell=pangu-ultra-moe-ep16.reason-saturated

python3 benchmark/sets.py --workload $cell --seeds 2147483832,2147483833,2147483834,71,72,73 --sets 1 --out $out > $out/table.txt 2>&1
grep -v "^set [12] seed" $out/table.txt | cut -c1-700 | tail -60
