#!/bin/bash
# PR 31, call f1 (one chip): chiprun --chips 1 --timeout 2700 -- bash scripts/chip_calls/pr31_f1_sweep_traced_pairs.sh
# (1) one expert layer in both forms over held experts x rows (pr31_form_sweep.py): what the rule's two conditions rest on;
# (2) a traced run of the routed cell on the change (the line the driver's traced run will carry);
# (3) parent, change, change, parent with --trace 0 in the routed cell, the two sides of a pair on one seed
#     (_checkout/parent: `git archive dfb687c`; the benchmark files are the same on both sides);
# (4) one parent / change pair of qwen25-3b.decode-saturated (a dense decoder: no expert layer).
out=$PWD/chiprun_out/pr31/${1:-f1}; mkdir -p $out
python3 scripts/chip_calls/pr31_form_sweep.py --out $out/sweep.json 2> $out/sweep.err | tee $out/sweep.txt | cut -c1-260
run() {  # side workload seed tag trace
  local dir=.; [ "$1" = parent ] && dir=_checkout/parent
  (cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace ${5:-0}) \
    > $out/$4_$1.out 2> $out/$4_$1.err
  echo "$4 $1 $2 seed $3 rc=$? $(tail -1 $out/$4_$1.out | cut -c1-${6:-520})"
}
routed=pangu-ultra-moe-ep16.reason-saturated
run change $routed 2147483911 traced 1 6000
run parent $routed 2147483913 p1; run change $routed 2147483913 p1
run change $routed 2147483915 p2; run parent $routed 2147483915 p2
run parent qwen25-3b.decode-saturated 2147483917 q1; run change qwen25-3b.decode-saturated 2147483917 q1
