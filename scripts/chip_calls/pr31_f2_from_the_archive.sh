#!/bin/bash
# PR 31, call f2 (one chip): chiprun --chips 1 --timeout 2700 -- bash scripts/chip_calls/pr31_f2_from_the_archive.sh
# the committed files alone, from `git archive $(git write-tree)` of the final tree unpacked into _checkout/final
# (_checkout/parent: `git archive dfb687c`):
# (1) the routed cell with --trace 1 (the line the driver's traced run will carry), and which form each program took;
# (2) change, parent, parent, change with --trace 0 in the routed cell, the two sides of a pair on one fresh seed;
# (3) one parent / change pair of qwen25-3b.chat-steady, the cell call f1 left out.
out=$PWD/chiprun_out/pr31/${1:-f2}; mkdir -p $out
run() {  # side workload seed tag trace width
  (cd _checkout/$1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace ${5:-0}) \
    > $out/$4_$1.out 2> $out/$4_$1.err
  echo "$4 $1 $2 seed $3 rc=$? $(tail -1 $out/$4_$1.out | cut -c1-${6:-520})"
}
routed=pangu-ultra-moe-ep16.reason-saturated
run final $routed 2147483921 traced 1 6000
grep -o '"[^"]*ragged[^"]*"' $out/traced_final.out | sort -u | sed 's/^/ragged-dot among device_ops: /'
run final $routed 2147483923 p3; run parent $routed 2147483923 p3
run parent $routed 2147483925 p4; run final $routed 2147483925 p4
run parent qwen25-3b.chat-steady 2147483927 s1; run final qwen25-3b.chat-steady 2147483927 s1
