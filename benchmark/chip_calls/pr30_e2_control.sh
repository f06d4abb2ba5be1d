#!/bin/bash
# PR 30, call e2 (one chip): chiprun --chips 1 --timeout 2400 -- bash benchmark/chip_calls/pr30_e2_control.sh
# the six limits read again with the cell on the default (sparse) dispatch, on eight seeds of their own: the
# program's numbers and the int8 control's per position, and judge's verdict on each under the file's limits
mkdir -p chiprun_out/pr30
python3 benchmark/chip_calls/pr30_look.py --workload pangu-ultra-moe-ep16.reason-saturated --seconds 25 --control 1 \
  --seeds ${SEEDS:-2147483881,2147483882,2147483883,2147483884,2147483885,2147483886,2147483887,2147483888} \
  --out chiprun_out/pr30/e2_control > chiprun_out/pr30/e2_control.log 2>&1
echo "rc=$?"; grep "^\[look.*{\"seed\"\|FAILED\|Error\|error" chiprun_out/pr30/e2_control.log | cut -c1-2600 | tail -40
