#!/bin/bash
# PR 37, call a1 (one chip): chiprun --chips 1 --timeout 2400 -- bash benchmark/chip_calls/pr37_a1_parent_and_bringup.sh
# (1) the PARENT (git archive of c263463 in _checkout/parent, this PR's BENCHMARK.json, benchmark/ and
# tests/benchmark/ laid over it, as the driver does) asked for the new cell: it must fail at once, not hang;
# (2) the new cell once from the working tree (TRACE=1: traced): does it come up, what does it read.
out=$PWD/chiprun_out/pr37/${TAG:-a1}; mkdir -p $out
cell=minicpm-sala-l12.longreason-saturated
seed=${SEED:-2147484501}
if [ "${PARENT:-1}" = 1 ]; then
  cp BENCHMARK.json _checkout/parent/ && cp -r benchmark _checkout/parent/ && cp -r tests/benchmark _checkout/parent/tests/
  ( cd _checkout/parent && t=$(date +%s) && timeout 300 python3 benchmark/run.py --workload $cell --seed $seed --seconds 51 --trace 0 \
      > $out/parent.out 2> $out/parent.err; echo "PARENT on $cell rc=$? after $(( $(date +%s) - t )) s: $(tail -2 $out/parent.err | cut -c1-300 | tr '\n' ' ')" )
fi
t=$(date +%s)
python3 benchmark/run.py --workload $cell --seed $seed --seconds ${SECONDS_:-51} --trace ${TRACE:-1} > $out/run.out 2> $out/run.err
echo "run.py $cell rc=$? after $(( $(date +%s) - t )) s: $(tail -1 $out/run.out | cut -c1-7000)"
grep -v "^\[bench.*request" $out/run.out | grep "gap mode\|per_layer\|end_to_end\|compil\|memory\|strateg\|setup\|correct\|module\|loaded\|window\|compar" | head -90 | cut -c1-400
tail -30 $out/run.err | cut -c1-400
