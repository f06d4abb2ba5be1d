#!/bin/bash
# PR 30, call e5 (one chip): chiprun --chips 1 --timeout 900 -- bash benchmark/chip_calls/pr30_e5_look_one_seed.sh [seed]
# the seed of e3's set 1 whose run read a DECIDED served_gap of 1.327 (limit 1.2): the same run through
# pr30_look.py, which keeps the comparison's numbers per position (which position, its margin, its neighbours)
mkdir -p chiprun_out/pr30
python3 benchmark/chip_calls/pr30_look.py --workload pangu-ultra-moe-ep16.reason-saturated --seconds 51 --control 0 \
  --seeds ${1:-2147483892} --out chiprun_out/pr30/e5_look > chiprun_out/pr30/e5_look.log 2>&1
echo "rc=$?"; grep "correctness:\|margin 0.005\|margin 0.01\|margin 0.02\|^\[look.*{\"seed\"" chiprun_out/pr30/e5_look.log | cut -c1-900
