#!/bin/bash
#   bash scripts/chip_calls/pr43_c2_second_set.sh   # one v5e chip, up to 1300 s
# A second set of runs of the new cell from the committed files (_checkout/final = `git archive
# $(git write-tree)` of the final tree), each on a seed of its own: five untraced (the spread of each
# end-to-end metric in a second set, as a check of the benchmark makes two) and, last, one traced (the
# per-layer metrics from the final tree).
out=chiprun_out/pr43/${TAG:-c2}
mkdir -p "$out"
cell=k-exaone-ep8.longctx-saturated
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
  grep -a "samples: token gaps" "$out/$5.out" | cut -c1-300
}
for i in 1 2 3 4 5; do
  [ $SECONDS -lt 1000 ] && run _checkout/final $cell $((2147485350 + i)) 0 set$i
done
[ $SECONDS -lt 1050 ] && run _checkout/final $cell 2147485356 1 traced
echo "done at ${SECONDS} s"
