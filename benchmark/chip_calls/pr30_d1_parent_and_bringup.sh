#!/bin/bash
# PR 30, call d1 (one chip): chiprun --chips 1 --timeout 1500 -- bash benchmark/chip_calls/pr30_d1_parent_and_bringup.sh
# (1) the PARENT (git archive of d4ab352 in _checkout/parent, this PR's benchmark files laid over it, as the
# driver does) asked for the new cell: it must fail at once, not hang; (2) the new cell once, untraced, from the
# working tree: does it come up, what does it read.
out=$PWD/chiprun_out/pr30/d1; mkdir -p $out
cell=pangu-ultra-moe-ep16.reason-saturated
( cd _checkout/parent && t=$(date +%s) && timeout 300 python3 benchmark/run.py --workload $cell --seed 2147483777 --seconds 51 --trace 0 \
    > $out/parent.out 2> $out/parent.err; echo "PARENT on $cell rc=$? after $(( $(date +%s) - t )) s: $(tail -2 $out/parent.err | cut -c1-300 | tr '\n' ' ')" )
python3 benchmark/run.py --workload $cell --seed 2147483777 --seconds ${SECONDS_:-51} --trace 0 > $out/run_t0.out 2> $out/run_t0.err
echo "run.py $cell rc=$? $(tail -1 $out/run_t0.out | cut -c1-3000)"
grep -v "^\[bench.*request" $out/run_t0.out | head -60 | cut -c1-400
tail -25 $out/run_t0.err | cut -c1-300
