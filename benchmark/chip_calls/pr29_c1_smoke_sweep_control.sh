#!/bin/bash
# PR 29, call c1 (one chip): chiprun --chips 1 --timeout 2400 -- bash benchmark/chip_calls/pr29_c1_smoke_sweep_control.sh
# does the revised harness run (one short run of each cell); the steady cell's knee at today's step;
# the program's and the control's readings of the comparison on three seeds in each cell
out=chiprun_out/pr29/c1; mkdir -p $out
for name in decode-saturated chat-steady; do
  python3 benchmark/run.py --workload qwen25-3b.$name --seed 2147483901 --seconds 20 --trace 0 > $out/smoke_$name.out 2> $out/smoke_$name.err
  echo "smoke $name rc=$?"; grep -v "^\[bench.*module\|^$" $out/smoke_$name.out | cut -c1-600 | tail -32; tail -12 $out/smoke_$name.err | cut -c1-300
done
python3 benchmark/sweep.py --config qwen25-3b --traffic chat-steady --seed 43 --seconds 40 --rates 6,7,8,9,10,11,12,13,14 > $out/sweep.out 2> $out/sweep.err
echo "sweep rc=$?"; grep "rate_per_s" $out/sweep.out | cut -c1-800; tail -5 $out/sweep.err | cut -c1-300
for name in decode-saturated chat-steady; do
  python3 benchmark/chip_calls/pr29_control.py --workload qwen25-3b.$name --seeds 2147483911,2147483913,77 --seconds 25 --out $out/control_$name.jsonl > $out/control_$name.out 2> $out/control_$name.err
  echo "control $name rc=$?"; grep "correctness\|compared" $out/control_$name.out | cut -c1-500; tail -5 $out/control_$name.err | cut -c1-300
done
