#!/bin/bash
#   bash scripts/chip_calls/pr43_c1_final.sh   # one v5e chip, up to 2820 s
# From the committed files alone: _checkout/final = `git archive $(git write-tree)` of the final tree,
# _checkout/parent = `git archive` of the parent with the final tree's BENCHMARK.json, benchmark/ and
# tests/benchmark/ laid over it.
# (1) a TRACED pair of mimo-v2-flash-ep16, parent then change, one seed: its walk is now models/window_full.py
#     and its ring commit lies under `layers.window`: tkg.attention_ms must read as before, and
#     tkg.window_attention_ms reads on both (on the parent without the commit, which lies outside the
#     container there); the parent's traced run also shows that the benchmark's new reader works on a program
#     without what this change adds;
# (2) six untraced runs of the new cell on six seeds while the time lasts (the spread of each end-to-end
#     metric; `correct` under the file's limits; memory_peak_bytes; kv.preemptions in the log).
out=chiprun_out/pr43/${TAG:-c1}
mkdir -p "$out"
cell=k-exaone-ep8.longctx-saturated
mimo=mimo-v2-flash-ep16.longctx-saturated
cp -r _checkout/final/benchmark _checkout/parent/ && cp _checkout/final/BENCHMARK.json _checkout/parent/ \
  && cp -r _checkout/final/tests/benchmark _checkout/parent/tests/
run() {  # <tree> <cell> <seed> <trace> <tag>
  t=$SECONDS
  (cd "$1" && timeout 600 python3 benchmark/run.py --workload "$2" --seed "$3" --seconds 51 --trace "$4") \
    > "$out/$5.out" 2> "$out/$5.err"
  echo "$5 seed $3 rc=$? after $((SECONDS - t)) s (at ${SECONDS} s)"
  python3 -c "
import json
d = json.loads(open('$out/$5.out').read().strip().splitlines()[-1])
print(' ', d['correct'], d.get('failed'), {k: v['value'] for k, v in d['metrics'].items()})
print('  device', d.get('device'))
print('  compared', {k: (v['value'], v['limit']) for k, v in d['compared'].items()})" 2>&1 | cut -c1-3000
  grep -a "kv.preemptions\|gap mode" "$out/$5.out" | cut -c1-200
}
run _checkout/parent $mimo 2147485341 1 parent-mimo
run _checkout/final $mimo 2147485341 1 change-mimo
grep -a "^region token_generation_model\|tkg.window_attention_ms" "$out/parent-mimo.err" "$out/change-mimo.err" \
  "$out/parent-mimo.out" "$out/change-mimo.out" | head -20 | cut -c1-220
for i in 1 2 3 4 5 6; do
  [ $SECONDS -lt 2380 ] && run _checkout/final $cell $((2147485330 + i)) 0 set$i
done
echo "done at ${SECONDS} s"
