#!/bin/bash
# (1) mimo's CTE[4096] and decode step timed in one process with the weights as held, laid out
# (the prefill without cross-program prefetch), and laid out with the prefill's prefetch
# (pr40_cte_ab.py); the script ends there unless the laid-out prefill is within 3 % of the held
# one; (2) untraced runs of this tree on the seeds of pr40_b1_final.sh's parent runs
# (mimo 2147485104, pangu 2147485108), whose parent readings that script gives.
#   bash scripts/chip_calls/pr40_c1_prefill.sh   # one v5e chip, up to 900 s
out=chiprun_out/pr40/c1
mkdir -p "$out"
python3 scripts/chip_calls/pr40_cte_ab.py mimo-v2-flash-ep16 4096 > "$out/cte_ab.out" 2> "$out/cte_ab.err"
rc=$?
echo "cte_ab rc=$rc at ${SECONDS}s"; grep -v '^ *$' "$out/cte_ab.out" | cut -c1-600
[ $rc = 0 ] || { tail -5 "$out/cte_ab.err"; exit 1; }
run() {  # <cell> <seed> <tag>
  python3 benchmark/run.py --workload "$1" --seed "$2" --seconds 51 --trace 0 > "$out/$3.out" 2> "$out/$3.err"
  echo "$3 rc=$? at ${SECONDS}s"
  python3 -c "
import json
d = json.loads(open('$out/$3.out').read().strip().splitlines()[-1])
print(' ', d['correct'], {k: round(v['value'], 4) for k, v in d['metrics'].items()})" 2>&1 | cut -c1-1500
}
run mimo-v2-flash-ep16.longctx-saturated 2147485104 change-mimo
run pangu-ultra-moe-ep16.reason-saturated 2147485108 change-pangu-2147485108
