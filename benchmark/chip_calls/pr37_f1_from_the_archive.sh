#!/bin/bash
# PR 37, call f1 (one chip): chiprun --chips 1 --timeout 3550 -- bash benchmark/chip_calls/pr37_f1_from_the_archive.sh
# The committed files alone: _checkout/final = `git archive $(git write-tree)` of the final tree; the parent =
# _checkout/parent (`git archive c263463` with this PR's BENCHMARK.json, benchmark/ and tests/benchmark/ laid over
# it, as the driver does). (1) the new cell on three seeds no run has seen, the first TRACED; (2) each cell the
# benchmark had, one TRACED pair parent against change on a seed of its own, the order alternating (their 15 step
# programs compile to the parent's HLO byte for byte, pr37_hlo_cmp.sh: what is left to move is the host's side).
out=$PWD/chiprun_out/pr37/${TAG:-f1}; mkdir -p $out
cp BENCHMARK.json _checkout/parent/ && cp -r benchmark _checkout/parent/ && cp -r tests/benchmark _checkout/parent/tests/
run() {  # side workload seed tag trace
  local dir=_checkout/final; [ "$1" = parent ] && dir=_checkout/parent
  (cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace ${5:-0}) \
    > $out/$4_$1.out 2> $out/$4_$1.err
  local rc=$?
  echo "$4 $1 $2 seed $3 rc=$rc $(tail -1 $out/$4_$1.out | cut -c1-${6:-2300})"
  grep "end_to_end\|gap mode" $out/$4_$1.out | cut -c12-200
  if [ "$1" = final ] && { [ $rc != 0 ] || ! tail -1 $out/$4_$1.out | grep -q '"correct": true'; }; then
    tail -40 $out/$4_$1.err; exit 1
  fi
}
new=minicpm-sala-l12.longreason-saturated
seed=${SEED0:-2147484541}
if [ "${NEW:-1}" = 1 ]; then
  if [ "${NEW_TRACED:-1}" = 1 ]; then run final $new $seed new_traced 1 6000; fi
  for i in $(seq 1 ${NEW_RUNS:-3}); do run final $new $((seed + i)) new_$((i + 1)); done
fi
sat=qwen25-3b.decode-saturated; steady=qwen25-3b.chat-steady; routed=pangu-ultra-moe-ep16.reason-saturated
mimo=mimo-v2-flash-ep16.longctx-saturated
seed=$((seed + 10)); first=parent
for w in ${CELLS:-$mimo $sat $routed $steady}; do
  second=final; [ $first = final ] && second=parent
  run $first $w $seed ${w%%.*}_${w##*.}_traced 1; run $second $w $seed ${w%%.*}_${w##*.}_traced 1
  seed=$((seed + 1)); first=$second
done
