#!/bin/bash
# PR 30, call e6 (one chip): chiprun --chips 1 --timeout 900 -- bash benchmark/chip_calls/pr30_e6_final_archive_traced.sh
# the committed files alone as the PR leaves them (_checkout/final = `git archive $(git write-tree)`, the limits
# as they stand): the benchmark's own command in the new cell, --trace 1, a fresh seed
out=$PWD/chiprun_out/pr30/e6; mkdir -p $out
cd _checkout/final || exit 9
python3 benchmark/run.py --workload pangu-ultra-moe-ep16.reason-saturated --seed 2147483899 --seconds 51 --trace 1 > $out/run_t1.out 2> $out/run_t1.err
echo "rc=$? $(tail -1 $out/run_t1.out | cut -c1-3600)"
