#!/bin/bash
# One traced run of mimo's cell on this tree with the program's INFO log (pr40_run.py: the load
# line and the region split), the prefill without the cross-program prefetch.
#   bash scripts/chip_calls/pr40_c3_traced_mimo.sh   # one v5e chip, up to 370 s
out=chiprun_out/pr40/c3
mkdir -p "$out"
python3 scripts/chip_calls/pr40_run.py --workload mimo-v2-flash-ep16.longctx-saturated --seed 2147485321 \
  --seconds 51 --trace 1 > "$out/traced-mimo.out" 2> "$out/traced-mimo.err"
echo "traced-mimo rc=$? at ${SECONDS}s"
grep -a "params: \|region .*\(unscoped\|sum\|attention:\|ffn:\)" "$out/traced-mimo.err" | cut -c1-300
python3 -c "
import json
d = json.loads(open('$out/traced-mimo.out').read().strip().splitlines()[-1])
print(d['correct'], {k: round(v['value'], 4) for k, v in d['metrics'].items()})" 2>&1 | cut -c1-3000
