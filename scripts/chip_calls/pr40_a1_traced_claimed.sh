#!/bin/bash
# One traced run of each claimed cell on this tree, with the program's INFO log (pr40_run.py):
# the load line "params: N leaves laid out ..." and the region split ("region ..." lines).
#   bash scripts/chip_calls/pr40_a1_traced_claimed.sh   # one v5e chip, up to 1500 s
out=chiprun_out/pr40/a1
mkdir -p "$out"
seed=${SEED0:-2147485001}
run() {  # <cell> <seed> <tag>
  python3 scripts/chip_calls/pr40_run.py --workload "$1" --seed "$2" --seconds 51 --trace 1 \
    > "$out/$3.out" 2> "$out/$3.err"
  echo "$3 rc=$? at ${SECONDS}s"
  grep -a "params: \|region .*token_generation.*\(unscoped\|sum\)\|loaded and warmed" "$out/$3.err" | cut -c1-400
  python3 -c "
import json
d = json.loads(open('$out/$3.out').read().strip().splitlines()[-1])
print(d['correct'], {k: round(v['value'], 4) for k, v in d['metrics'].items()})" 2>&1 | cut -c1-3000
}
run minicpm-sala-l12.longreason-saturated $((seed)) sala
run mimo-v2-flash-ep16.longctx-saturated $((seed + 1)) mimo
