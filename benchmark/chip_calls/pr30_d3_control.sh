#!/bin/bash
# PR 30, call d3 (one chip): chiprun --chips 1 --timeout 3000 -- bash benchmark/chip_calls/pr30_d3_control.sh
# the readings the six limits of the new configuration are set from: a dozen seeds, the cell's own traffic in a
# 25 s window, the program's numbers and the int8 control's, per position (pr30_look.py keeps judge's arguments)
python3 benchmark/chip_calls/pr30_look.py --workload pangu-ultra-moe-ep16.reason-saturated --seconds 25 --control 1 \
  --seeds ${SEEDS:-2147483811,2147483812,2147483813,2147483814,2147483815,2147483816,2147483817,2147483818,2147483819,2147483820,2147483821,2147483822} \
  --out chiprun_out/pr30/d3_control > chiprun_out_d3.log 2>&1
rc=$?; mkdir -p chiprun_out/pr30; mv chiprun_out_d3.log chiprun_out/pr30/d3_control.log
echo "rc=$rc"; grep "compared\|set-up\|comparison \|tokens in window\|FAILED\|Error\|error" chiprun_out/pr30/d3_control.log | cut -c1-260 | tail -150
