#!/bin/bash
# PR 33 (one chip): chiprun --chips 1 --timeout 3000 -- bash scripts/chip_calls/pr33_h1_traced_and_pairs.sh [tag] [change dir] [cells]
# For each cell named (default: all three): one traced run of the change, one traced run of the parent, then parent /
# change pairs (--trace 0, one seed a pair, the order alternating). Parent = _checkout/parent (`git archive 5aee92c`
# with this PR's BENCHMARK.json, benchmark/ and tests/benchmark/ laid over it, as the driver does); the change is the
# tree itself, or the directory given (_checkout/final = `git archive $(git write-tree)`, the committed files alone).
out=$PWD/chiprun_out/pr33/${1:-h1}; change=${2:-.}; cells=${3:-sat routed steady}; mkdir -p $out
run() {  # side workload seed tag trace
  local dir=$change; [ "$1" = parent ] && dir=_checkout/parent
  (cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace ${5:-0}) \
    > $out/$4_$1.out 2> $out/$4_$1.err
  local rc=$?
  echo "$4 $1 $2 seed $3 rc=$rc $(tail -1 $out/$4_$1.out | cut -c1-${6:-560})"
  # a change that does not run, or reads incorrect, is not worth the rest of the call
  if [ "$1" = change ] && { [ $rc != 0 ] || ! tail -1 $out/$4_$1.out | grep -q '"correct": true'; }; then
    tail -40 $out/$4_$1.err; exit 1
  fi
}
sat=qwen25-3b.decode-saturated; steady=qwen25-3b.chat-steady; routed=pangu-ultra-moe-ep16.reason-saturated
seed=${SEED0:-2147484101}
for c in $cells; do
  case $c in sat) w=$sat;; steady) w=$steady;; routed) w=$routed;; esac
  run change $w $seed ${c}_traced 1 6000
  [ -n "$TRACE_PARENT" ] && run parent $w $seed ${c}_traced 1 6000
  for k in $(seq 1 ${PAIRS:-1}); do
    s=$((seed + k))
    if [ $((k % 2)) = 1 ]; then run parent $w $s ${c}_p$k; run change $w $s ${c}_p$k
    else run change $w $s ${c}_p$k; run parent $w $s ${c}_p$k; fi
  done
  seed=$((seed + 10))
done
