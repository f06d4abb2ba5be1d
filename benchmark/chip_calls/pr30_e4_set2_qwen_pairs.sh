#!/bin/bash
# PR 30, call e4 (one chip): chiprun --chips 1 --timeout 3300 -- bash benchmark/chip_calls/pr30_e4_set2_qwen_pairs.sh
# set 2 of the new cell (the same six seeds as e3's set 1, from the committed files alone), then both
# qwen25-3b cells, parent and change on the same seeds (_checkout/parent: `git archive d4ab352` with this
# PR's benchmark files laid over it, as the driver does; change = _checkout/final)
out=$PWD/chiprun_out/pr30/e4; mkdir -p $out
cell=pangu-ultra-moe-ep16.reason-saturated
(cd _checkout/final && python3 benchmark/sets.py --workload $cell --seeds 2147483892,2147483893,2147483894,81,82,83 --sets 1 --out $out/set2 > $out/set2.txt 2>&1)
grep -v "^set [12] seed" $out/set2.txt | cut -c1-400 | tail -40
grep -h "samples: token gaps" $out/set2/*.out | sed 's/.*p99 /p99 /'
run() {  # side cell seed tag
  (cd _checkout/$1 && python3 benchmark/run.py --workload qwen25-3b.$2 --seed $3 --seconds 51 --trace 0) \
    > $out/$4_$1_$2.out 2> $out/$4_$1_$2.err
  echo "$4 $1 $2 seed $3 rc=$? $(tail -1 $out/$4_$1_$2.out | cut -c1-420)"
}
run parent decode-saturated 2147483895 p1; run final decode-saturated 2147483895 p1
run final chat-steady 2147483896 p1; run parent chat-steady 2147483896 p1
