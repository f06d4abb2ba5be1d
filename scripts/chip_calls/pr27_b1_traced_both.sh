#!/bin/bash
# PR 27, call b1 (one chip): chiprun --chips 1 --timeout 1500 -- bash scripts/chip_calls/pr27_b1_traced_both.sh [b1|b2]
# the first look at the change: a traced run of each cell, kept whole (steps.json, look.txt, split.json, line.json),
# to read tkg.device_ms, the ten longest device ops and what the row write costs on the carried pool.
out=chiprun_out/pr27/${1:-b1}; mkdir -p $out
for cell in decode-saturated:2147483811 chat-steady:2147483813; do
  name=${cell%%:*}; seed=${cell##*:}
  python3 benchmark/chip_calls/pr26_traced_run.py --workload qwen25-3b.$name --seed $seed --seconds 51 --trace 1 \
    --out $out/traced_$name 2> $out/traced_$name.err
  echo "traced $name rc=$?"
  tail -3 $out/traced_$name/log.txt | cut -c1-3000
done
