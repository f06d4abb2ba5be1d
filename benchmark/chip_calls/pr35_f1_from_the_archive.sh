#!/bin/bash
# PR 35, call f1 (one chip): chiprun --chips 1 --timeout 2400 -- bash benchmark/chip_calls/pr35_f1_from_the_archive.sh
# the committed files alone (_checkout/final = `git archive $(git write-tree)`): the new cell once traced and twice
# untraced, three seeds used nowhere before
out=$PWD/chiprun_out/pr35/${TAG:-f1}; mkdir -p $out
cell=mimo-v2-flash-ep16.longctx-saturated
cd _checkout/final || exit 1
k=0
for seed in ${SEEDS:-2147484501 2147484502 2147484503}; do
  trace=0; [ $k = 0 ] && trace=1
  python3 benchmark/run.py --workload $cell --seed $seed --seconds 51 --trace $trace > $out/run_$seed.out 2> $out/run_$seed.err
  echo "archive $cell seed $seed trace $trace rc=$? $(tail -1 $out/run_$seed.out | cut -c1-2600)"
  grep "gap mode\|compared\|comparison with" $out/run_$seed.out | cut -c1-260
  k=$((k + 1))
done
