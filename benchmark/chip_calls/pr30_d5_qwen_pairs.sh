#!/bin/bash
# PR 30, call d5 (one chip): chiprun --chips 1 --timeout 3000 -- bash benchmark/chip_calls/pr30_d5_qwen_pairs.sh
# both qwen25-3b cells, parent and change on the same seeds, --trace 0 (_checkout/parent: `git archive d4ab352`
# with this PR's benchmark files laid over it, as the driver does), and one TRACED run of an old cell on the
# parent with this PR's readers (they must find nothing to read there, and not raise)
out=$PWD/chiprun_out/pr30/d5; mkdir -p $out
run() {  # side cell seed tag trace
  local dir=.; [ "$1" = parent ] && dir=_checkout/parent
  (cd $dir && python3 benchmark/run.py --workload qwen25-3b.$2 --seed $3 --seconds 51 --trace ${5:-0}) \
    > $out/$4_$1_$2.out 2> $out/$4_$1_$2.err
  echo "$4 $1 $2 seed $3 rc=$? $(tail -1 $out/$4_$1_$2.out | cut -c1-${6:-420})"
}
run parent decode-saturated 2147483841 p1; run change decode-saturated 2147483841 p1
run change decode-saturated 2147483843 p2; run parent decode-saturated 2147483843 p2
run parent chat-steady 2147483845 p1; run change chat-steady 2147483845 p1
run parent decode-saturated 2147483847 traced 1 2400
