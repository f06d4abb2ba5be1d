#!/bin/bash
# PR 35, call c1 / c2 (one chip): chiprun --chips 1 --timeout 3500 -- bash benchmark/chip_calls/pr35_c1_set.sh
# the new cell: one set of untraced runs at the manifest's run_seconds, each on a seed of its own, none used
# while the code was written (the spreads against a fifth and a half of the bounds; every run's `compared`)
out=chiprun_out/pr35/${TAG:-c1}; mkdir -p $out
cell=mimo-v2-flash-ep16.longctx-saturated
python3 benchmark/sets.py --workload $cell --seeds ${SEEDS:-2147484321,2147484322,2147484323,81,82,83} --sets 1 --out $out > $out/table.txt 2>&1
grep -v "^set [12] seed" $out/table.txt | cut -c1-700 | tail -70
