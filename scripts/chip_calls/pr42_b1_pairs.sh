#!/bin/bash
#   bash scripts/chip_calls/pr42_b1_pairs.sh   # one v5e chip, up to 1800 s
# From the committed files alone: _checkout/final = `git archive $(git write-tree)` of the final tree,
# _checkout/parent = `git archive` of the parent commit. Untraced runs of solar-open2-ep8.reason-saturated in
# the order parent, change, change, parent, each pair on a seed of its own (a parent/change comparison in small),
# while the budget of the call lasts.
out=chiprun_out/pr42/b1
mkdir -p "$out"
cell=solar-open2-ep8.reason-saturated
run() {  # <tree> <seed> <tag>
  t=$SECONDS
  (cd "$1" && timeout 420 python3 benchmark/run.py --workload $cell --seed "$2" --seconds 51 --trace 0) \
    > "$out/$3.out" 2> "$out/$3.err"
  echo "$3 seed $2 rc=$? after $((SECONDS - t)) s (at ${SECONDS} s)"
  python3 -c "
import json
d = json.loads(open('$out/$3.out').read().strip().splitlines()[-1])
m = d['metrics']
print(' ', d['correct'], d.get('failed'), {k: m[k]['value'] for k in ('out_tok_s', 'tpot_p99_ms', 'setup_s', 'engine.step_wall_ms') if k in m})
print('  memory_peak_bytes', d.get('device', {}).get('memory_peak_bytes'))
print('  compared', {k: (v['value'], v['limit']) for k, v in d['compared'].items()})" 2>&1 | cut -c1-2000
}
seed=${SEED0:-2147485211}
for i in 0 1 2; do
  [ $SECONDS -lt 1000 ] || break
  s=$((seed + i))
  if [ $((i % 2)) -eq 0 ]; then
    run _checkout/parent $s parent-$i; run _checkout/final $s change-$i
  else
    run _checkout/final $s change-$i; run _checkout/parent $s parent-$i
  fi
done
echo "done at ${SECONDS} s"
