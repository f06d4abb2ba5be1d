#!/bin/bash
# (1) mimo's CTE[4096] and decode step timed with each subset of the laid-out leaves, and with
# none (pr40_cte_leaves.py); (2) untraced runs of this tree on the seeds of pr40_b1_final.sh's
# parent runs (mimo 2147485104, pangu 2147485108), whose parent readings that script gives.
#   bash scripts/chip_calls/pr40_c2_leaves.sh   # one v5e chip, up to 900 s
out=chiprun_out/pr40/c2
mkdir -p "$out"
python3 scripts/chip_calls/pr40_cte_leaves.py mimo-v2-flash-ep16 4096 > "$out/leaves.out" 2> "$out/leaves.err"
echo "leaves rc=$? at ${SECONDS}s"; cut -c1-400 "$out/leaves.out"
run() {  # <cell> <seed> <tag>
  python3 benchmark/run.py --workload "$1" --seed "$2" --seconds 51 --trace 0 > "$out/$3.out" 2> "$out/$3.err"
  echo "$3 rc=$? at ${SECONDS}s"
  python3 -c "
import json
d = json.loads(open('$out/$3.out').read().strip().splitlines()[-1])
print(' ', d['correct'], {k: round(v['value'], 4) for k, v in d['metrics'].items()})" 2>&1 | cut -c1-1500
}
run mimo-v2-flash-ep16.longctx-saturated 2147485104 change-mimo-2147485104
run pangu-ultra-moe-ep16.reason-saturated 2147485108 change-pangu-2147485108
