#!/bin/bash
# PR 26, call a1 (one chip): chiprun --chips 1 --timeout 1500 -- bash benchmark/chip_calls/pr26_a1_traced_both.sh
# the first look at a trace with the program's own spans and names: one traced run of each cell, kept whole
# (flight records, the trace read by hand, idle time by phase, a cut of a few steps for tests/benchmark/)
for cell in decode-saturated:2147483707 chat-steady:2147483709; do
  name=${cell%%:*}; seed=${cell##*:}
  python3 benchmark/chip_calls/pr26_traced_run.py --workload qwen25-3b.$name --seed $seed --seconds 51 --trace 1 \
    --out chiprun_out/pr26/a1_$name 2> chiprun_out_err_$name.txt
  echo "$name rc=$?"
  mv chiprun_out_err_$name.txt chiprun_out/pr26/a1_$name/err.txt
  tail -3 chiprun_out/pr26/a1_$name/err.txt | cut -c1-400
done
