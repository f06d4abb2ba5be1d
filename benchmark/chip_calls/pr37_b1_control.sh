#!/bin/bash
# PR 37, call b1 (one chip): chiprun --chips 1 --timeout 1500 -- bash benchmark/chip_calls/pr37_b1_control.sh
# the upper reading of probe_mse: the reference over int8 weights in the program's place at the probe, on
# five seeds of their own (pr37_control.py: no programs, no window)
mkdir -p chiprun_out/pr37
python3 benchmark/chip_calls/pr37_control.py --workload minicpm-sala-l12.longreason-saturated \
  --seeds ${SEEDS:-2147484511,2147484512,2147484513,2147484514,2147484515} \
  --out chiprun_out/pr37/${TAG:-b1}_control.jsonl 2>&1 | grep "control\|Error\|error" | cut -c1-600
