#!/bin/bash
# PR 32, call g1 (one chip): chiprun --chips 1 --timeout 3000 -- bash scripts/chip_calls/pr32_g1_traced_and_pairs.sh [tag] [change dir]
# For each of the three cells: one traced run of the change, then one parent / change pair (--trace 0, one seed a pair,
# the order alternating from cell to cell). Parent = _checkout/parent (`git archive f1e11af`); the change is the tree
# itself, or the directory given (call g2: _checkout/final = `git archive $(git write-tree)`, the committed files alone).
# The benchmark's files are the same on both sides. No gain is claimed: the prediction is that nothing moves.
out=$PWD/chiprun_out/pr32/${1:-g1}; change=${2:-.}; mkdir -p $out
run() {  # side workload seed tag trace
  local dir=$change; [ "$1" = parent ] && dir=_checkout/parent
  (cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace ${5:-0}) \
    > $out/$4_$1.out 2> $out/$4_$1.err
  echo "$4 $1 $2 seed $3 rc=$? $(tail -1 $out/$4_$1.out | cut -c1-${6:-560})"
}
sat=qwen25-3b.decode-saturated; steady=qwen25-3b.chat-steady; routed=pangu-ultra-moe-ep16.reason-saturated
run change $sat 2147484011 sat_traced 1 6000
run parent $sat 2147484013 sat; run change $sat 2147484013 sat
run change $steady 2147484015 steady_traced 1 6000
run change $steady 2147484017 steady; run parent $steady 2147484017 steady
run change $routed 2147484019 routed_traced 1 6000
run parent $routed 2147484021 routed; run change $routed 2147484021 routed
