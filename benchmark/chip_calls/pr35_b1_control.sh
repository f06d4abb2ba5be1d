#!/bin/bash
# PR 35, call b1 (one chip): chiprun --chips 1 --timeout 3000 -- bash benchmark/chip_calls/pr35_b1_control.sh
# the six limits' two readings: the program's numbers and the int8 control's PER POSITION (pr30_look.py keeps
# correctness.judge's arguments), on seeds of their own, 25 s windows at the cell's own load, and judge's
# verdict on each under the file's limits
mkdir -p chiprun_out/pr35
python3 benchmark/chip_calls/pr30_look.py --workload mimo-v2-flash-ep16.longctx-saturated --seconds ${SECONDS_:-25} --control 1 \
  --seeds ${SEEDS:-2147484311,2147484312,2147484313,2147484314,2147484315} \
  --out chiprun_out/pr35/${TAG:-b1}_control > chiprun_out/pr35/${TAG:-b1}_control.log 2>&1
echo "rc=$?"; grep "^\[look.*{\"seed\"\|FAILED\|Error\|error\|margin 0.0\(05\|1\|2\)" chiprun_out/pr35/${TAG:-b1}_control.log | cut -c1-2600 | tail -70
