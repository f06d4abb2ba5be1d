#!/bin/bash
#   bash scripts/chip_calls/pr42_a1_ab_traced.sh   # one v5e chip, up to 1500 s
# (1) pr42_form_ab.py: one Solar-Open2 expert layer (40 of 320 held, top 8) in the three pinned forms at
#     16-1024 rows, the same layer three times in a scan, and 24 / 32 held at 128 and 256 rows;
# (2) one traced run (through pr42_run.py: the expert form each program took) of solar-open2-ep8.reason-saturated on this tree, then on the parent
#     (_checkout/parent = `git archive` of the parent commit), the same seed: the region lines of the
#     token-generation program and of the largest prefill, and every metric of the result line.
out=chiprun_out/pr42/a1
mkdir -p "$out"
cell=solar-open2-ep8.reason-saturated
timeout 420 python3 scripts/chip_calls/pr42_form_ab.py --out "$out/ab.json" 2> "$out/ab.err"
echo "ab rc=$? at ${SECONDS}s"
run() {  # <tree> <seed> <trace> <tag>
  t=$SECONDS
  (cd "$1" && timeout 600 python3 "$OLDPWD/scripts/chip_calls/pr42_run.py" --workload $cell --seed "$2" --seconds 51 --trace "$3") \
    > "$out/$4.out" 2> "$out/$4.err"
  echo "$4 seed $2 rc=$? after $((SECONDS - t)) s (at ${SECONDS} s)"
  grep -a "^region \|loaded and warmed\|ragged\|^expert form" "$out/$4.err" | cut -c1-220 | head -40
  python3 -c "
import json
d = json.loads(open('$out/$4.out').read().strip().splitlines()[-1])
print(' ', d['correct'], d.get('failed'), {k: v['value'] for k, v in d['metrics'].items()})
print('  device', d.get('device'))
print('  compared', {k: (v['value'], v['limit']) for k, v in d['compared'].items()})" 2>&1 | cut -c1-3500
}
run . 2147485201 1 change-traced
run _checkout/parent 2147485201 1 parent-traced
echo "done at ${SECONDS} s"
