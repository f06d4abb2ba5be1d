#!/bin/bash
# PR 30, call e3 (one chip): chiprun --chips 1 --timeout 3000 -- bash benchmark/chip_calls/pr30_e3_archive_traced_set.sh
# the committed files alone (`git archive $(git write-tree)` unpacked into _checkout/final), the cell on the
# default dispatch: one TRACED run with an EMPTY compile cache of its own (what set-up costs cold), then
# set 1 of 6 untraced runs at the manifest's run_seconds, each on a seed of its own
out=$PWD/chiprun_out/pr30/e3; mkdir -p $out
cell=pangu-ultra-moe-ep16.reason-saturated
cd _checkout/final || exit 9
JAX_COMPILATION_CACHE_DIR=/tmp/pr30_cold_cache python3 benchmark/run.py --workload $cell --seed 2147483891 --seconds 51 --trace 1 \
  > $out/cold_t1.out 2> $out/cold_t1.err
echo "cold traced rc=$? $(tail -1 $out/cold_t1.out | cut -c1-3800)"
grep "window \|loaded\|per_layer\|gap mode" $out/cold_t1.out | cut -c1-300
python3 benchmark/sets.py --workload $cell --seeds 2147483892,2147483893,2147483894,81,82,83 --sets 1 --out $out/set1 > $out/set1.txt 2>&1
grep -v "^set [12] seed" $out/set1.txt | cut -c1-400 | tail -40
grep -h "samples: token gaps" $out/set1/*.out | sed 's/.*p99 /p99 /'
