#!/bin/bash
# PR 35 (one chip): chiprun --chips 1 --timeout 3400 -- bash benchmark/chip_calls/pr35_d1_old_cells_pairs.sh [tag] [change dir] [cells]
# The three cells the benchmark had, parent against change: for each cell named (default: all three) one TRACED pair
# on one seed (kernel.paged_decode_ms, tkg.device_ms side by side) and one untraced pair on another (the end-to-end
# metrics), the order alternating. Parent = _checkout/parent (`git archive 3664d80` with this PR's BENCHMARK.json,
# benchmark/ and tests/benchmark/ laid over it, as the driver does); the change is the tree itself, or the directory
# given (_checkout/final = `git archive $(git write-tree)`, the committed files alone).
out=$PWD/chiprun_out/pr35/${1:-d1}; change=${2:-.}; cells=${3:-sat steady routed}; mkdir -p $out
run() {  # side workload seed tag trace
  local dir=$change; [ "$1" = parent ] && dir=_checkout/parent
  (cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace ${5:-0}) \
    > $out/$4_$1.out 2> $out/$4_$1.err
  local rc=$?
  echo "$4 $1 $2 seed $3 rc=$rc $(tail -1 $out/$4_$1.out | cut -c1-${6:-560})"
  if [ "$1" = change ] && { [ $rc != 0 ] || ! tail -1 $out/$4_$1.out | grep -q '"correct": true'; }; then
    tail -40 $out/$4_$1.err; exit 1
  fi
}
sat=qwen25-3b.decode-saturated; steady=qwen25-3b.chat-steady; routed=pangu-ultra-moe-ep16.reason-saturated
seed=${SEED0:-2147484401}
for c in $cells; do
  case $c in sat) w=$sat;; steady) w=$steady;; routed) w=$routed;; esac
  run change $w $seed ${c}_traced 1 1900; run parent $w $seed ${c}_traced 1 1900
  run parent $w $((seed + 1)) ${c}_p1; run change $w $((seed + 1)) ${c}_p1
  seed=$((seed + 10))
done
