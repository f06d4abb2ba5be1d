#!/bin/bash
# PR 37, call b2 (one chip): chiprun --chips 1 --timeout 1800 -- bash benchmark/chip_calls/pr37_b2_probe.sh
# probe_mse / probe_diff of the program and of the int8 control side by side, per position, on twelve seeds
# (six of them call c1's, read there before the lightning layers kept float32 from their projections on)
mkdir -p chiprun_out/pr37
python3 benchmark/chip_calls/pr37_probe.py --workload minicpm-sala-l12.longreason-saturated \
  --seeds ${SEEDS:-2147484521,2147484522,2147484523,2147484524,2147484525,2147484526,2147484531,2147484532,2147484533,2147484534,2147484535,2147484536} \
  --out chiprun_out/pr37/${TAG:-b2}_probe 2>&1 | grep "probe \|Error\|error" | cut -c1-900
