#!/bin/bash
# From the committed files alone: _checkout/parent = `git archive 06b09cc`,
# _checkout/final = `git archive $(git write-tree)` of the final tree.
# (1) a traced run of the claimed cells on the final tree with the program's INFO log (pr40_run.py:
#     the load line "params: ... laid out ..."; the "region" lines); a run that fails or reads
#     incorrect ends the call; (2) untraced pairs on one seed each, parent then change, the claimed
#     cells first; (3) a traced run and a pair of each other configuration while the budget lasts.
#   bash scripts/chip_calls/pr40_b1_final.sh   # one v5e chip, up to 3000 s
out=chiprun_out/pr40/b1
mkdir -p "$out"
here=$PWD
seed=${SEED0:-2147485101}
run() {  # <tree> <cell> <seed> <trace> <tag>
  if [ "$4" = 1 ]; then
    (cd "$1" && python3 "$here/scripts/chip_calls/pr40_run.py" --workload "$2" --seed "$3" --seconds 51 --trace 1) \
      > "$out/$5.out" 2> "$out/$5.err"
  else
    (cd "$1" && python3 benchmark/run.py --workload "$2" --seed "$3" --seconds 51 --trace 0) \
      > "$out/$5.out" 2> "$out/$5.err"
  fi
  rc=$?
  echo "$5 rc=$rc at ${SECONDS}s $(grep -a 'params: ' "$out/$5.err" | cut -c1-300)"
  python3 -c "
import json
d = json.loads(open('$out/$5.out').read().strip().splitlines()[-1])
print(' ', d['correct'], {k: round(v['value'], 4) for k, v in d['metrics'].items()})
raise SystemExit(0 if d['correct'] else 1)" 2>&1 | cut -c1-2500
  return $(( rc != 0 || ${PIPESTATUS[0]} != 0 ))
}
cells=(minicpm-sala-l12.longreason-saturated mimo-v2-flash-ep16.longctx-saturated
       qwen25-3b.decode-saturated pangu-ultra-moe-ep16.reason-saturated)
run _checkout/final "${cells[0]}" $((seed)) 1 traced-sala || exit 1
run _checkout/parent "${cells[0]}" $((seed + 1)) 0 parent-sala
run _checkout/final "${cells[0]}" $((seed + 1)) 0 change-sala
run _checkout/final "${cells[1]}" $((seed + 2)) 1 traced-mimo || exit 1
run _checkout/parent "${cells[1]}" $((seed + 3)) 0 parent-mimo
run _checkout/final "${cells[1]}" $((seed + 3)) 0 change-mimo
i=4
for tag in qwen pangu; do
  cell=${cells[$((i / 2))]}
  if [ "$SECONDS" -gt "${BUDGET_S:-2100}" ]; then echo "skipped $cell at ${SECONDS}s"; i=$((i + 2)); continue; fi
  run _checkout/final "$cell" $((seed + i)) 1 "traced-$tag"
  run _checkout/parent "$cell" $((seed + i + 1)) 0 "parent-$tag"
  run _checkout/final "$cell" $((seed + i + 1)) 0 "change-$tag"
  i=$((i + 2))
done
