#!/bin/bash
# PR 29, call c4 (one chip): chiprun --chips 1 --timeout 3000 -- bash benchmark/chip_calls/pr29_c4_saturated_sets.sh
# two sets of 6 runs of the saturated cell (the same seeds in both), its second and third traced run,
# the steady cell's third, and three short runs of the saturated cell on further seeds (correct on a dozen)
out=chiprun_out/pr29/c4; mkdir -p $out
python3 benchmark/sets.py --workload qwen25-3b.decode-saturated --seeds 61,62,63,64,2147483941,2147483943 --sets 2 --out $out > $out/table.txt 2>&1
for cell in decode-saturated:2147483945 decode-saturated:65 chat-steady:66; do
  name=${cell%%:*}; seed=${cell##*:}
  python3 benchmark/run.py --workload qwen25-3b.$name --seed $seed --seconds 51 --trace 1 > $out/traced_${name}_$seed.out 2> $out/traced_${name}_$seed.err
  echo "traced $name $seed rc=$?"; tail -1 $out/traced_${name}_$seed.out | cut -c1-2600
done
for seed in 67 68 2147483947; do
  python3 benchmark/run.py --workload qwen25-3b.decode-saturated --seed $seed --seconds 15 --trace 0 > $out/short_$seed.out 2> $out/short_$seed.err
  echo "short $seed rc=$? $(tail -1 $out/short_$seed.out | cut -c1-700)"
done
grep -v "^set [12] seed" $out/table.txt | cut -c1-700 | tail -40
