#!/bin/bash
#   bash scripts/chip_calls/pr42_c1_other_cells.sh   # one v5e chip, up to 1200 s
# The two other configurations whose programs ask ops/moe.py expert_form (shares of 16 of 256, top 8, which
# choose as before at every bucket and compile to the parent's HLO byte for byte: pr42_hlo_cmp.out): one
# untraced pair each, parent then change, from the committed files (_checkout/final = `git archive
# $(git write-tree)`, _checkout/parent = `git archive` of the parent commit).
out=chiprun_out/pr42/c1
mkdir -p "$out"
run() {  # <tree> <cell> <seed> <tag>
  t=$SECONDS
  (cd "$1" && timeout 420 python3 benchmark/run.py --workload "$2" --seed "$3" --seconds 51 --trace 0) \
    > "$out/$4.out" 2> "$out/$4.err"
  echo "$4 seed $3 rc=$? after $((SECONDS - t)) s (at ${SECONDS} s)"
  python3 -c "
import json
d = json.loads(open('$out/$4.out').read().strip().splitlines()[-1])
m = d['metrics']
print(' ', d['correct'], d.get('failed'), {k: m[k]['value'] for k in ('out_tok_s', 'tpot_p99_ms', 'setup_s', 'engine.step_wall_ms') if k in m})" 2>&1 | cut -c1-1000
}
run _checkout/parent pangu-ultra-moe-ep16.reason-saturated 2147485221 parent-pangu
run _checkout/final pangu-ultra-moe-ep16.reason-saturated 2147485221 change-pangu
run _checkout/parent mimo-v2-flash-ep16.longctx-saturated 2147485222 parent-mimo
run _checkout/final mimo-v2-flash-ep16.longctx-saturated 2147485222 change-mimo
echo "done at ${SECONDS} s"
