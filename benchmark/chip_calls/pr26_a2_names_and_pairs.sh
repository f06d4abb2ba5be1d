#!/bin/bash
# PR 26, call a2 (one chip): chiprun --chips 1 --timeout 3300 -- bash benchmark/chip_calls/pr26_a2_names_and_pairs.sh
# (1) a traced run of each cell on the change, kept whole (the kernels' and scopes' names now reach the HLO);
# (2) a traced run of one cell on the PARENT commit with this PR's benchmark files laid over it
#     (_checkout/parent: `git archive <parent>` + BENCHMARK.json, benchmark/, tests/benchmark/ of this tree):
#     the new readers must find nothing there and raise nothing;
# (3) parent, change, change, parent with --trace 0 in each cell, the two sides of a pair on one seed.
out=chiprun_out/pr26/a2; mkdir -p $out
for cell in decode-saturated:2147483711 chat-steady:2147483713; do
  name=${cell%%:*}; seed=${cell##*:}
  python3 benchmark/chip_calls/pr26_traced_run.py --workload qwen25-3b.$name --seed $seed --seconds 51 --trace 1 \
    --out $out/traced_$name 2> $out/traced_$name.err
  echo "traced $name rc=$?"
done
(cd _checkout/parent && python3 benchmark/run.py --workload qwen25-3b.chat-steady --seed 2147483715 --seconds 51 --trace 1) \
  > $out/parent_traced_chat-steady.out 2> $out/parent_traced_chat-steady.err
echo "parent traced rc=$?"; tail -1 $out/parent_traced_chat-steady.out | cut -c1-1500
run() {  # side cell seed tag
  local dir=.; [ "$1" = parent ] && dir=_checkout/parent
  (cd $dir && python3 benchmark/run.py --workload qwen25-3b.$2 --seed $3 --seconds 51 --trace 0) \
    > $out/$4_$1_$2.out 2> $out/$4_$1_$2.err
  echo "$4 $1 $2 seed $3 rc=$? $(tail -1 $out/$4_$1_$2.out | cut -c1-400)"
}
for cell in decode-saturated chat-steady; do
  run parent $cell 2147483721 p1; run change $cell 2147483721 p1
  run change $cell 2147483723 p2; run parent $cell 2147483723 p2
done
grep -h "end_to_end\|per_layer engine\|per_layer dispatch\|per_layer sched.schedule" $out/p?_*.out | sed 's/^\[bench *[0-9.]*s\]//' | sort | uniq -c | head -0
for f in $out/p?_*.out; do echo "== $f"; grep "end_to_end\|engine.step_wall_ms\|engine.host_ms\|dispatch.host_ms" $f | sed 's/^\[bench *[0-9.]*s\]//' | tr '\n' ';'; echo; done
