#!/bin/bash
# PR 24, second session, call 1 (one chip): chiprun --chips 1 --timeout 900 -- bash benchmark/chip_calls/pr24_r1_smoke_and_sweep.sh
# call 1: a short traced run of the saturated cell (does the revised harness run?), then the sweep
mkdir -p chiprun_out/r1
python3 benchmark/run.py --workload qwen25-3b.decode-saturated --seed 2147483659 --seconds 20 --trace 1 > chiprun_out/r1/smoke_sat.out 2> chiprun_out/r1/smoke_sat.err
rc=$?; echo "smoke rc=$rc"; grep -v "^\[bench.*module\|^$" chiprun_out/r1/smoke_sat.out | cut -c1-1500 | tail -40
if [ $rc -ne 0 ]; then tail -30 chiprun_out/r1/smoke_sat.err; exit 1; fi
python3 benchmark/sweep.py --config qwen25-3b --traffic chat-steady --seed 41 --seconds 30 --rates 4,5,5.5,6,6.5,7,8 > chiprun_out/r1/sweep.out 2> chiprun_out/r1/sweep.err
echo "sweep rc=$?"; grep "rate_per_s" chiprun_out/r1/sweep.out | cut -c1-700; tail -5 chiprun_out/r1/sweep.err
