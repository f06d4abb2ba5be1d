#!/bin/bash
#   bash scripts/chip_calls/pr43_a1_bringup_probe.sh   # one v5e chip, up to 1500 s
# (1) the new cell once, TRACED, from the working tree: does it come up, what does it read, the memory peak;
# (2) the PARENT (git archive of the parent commit in _checkout/parent, this tree's BENCHMARK.json, benchmark/
#     and tests/benchmark/ laid over it, as a check of the benchmark does) asked for the new cell: it must fail
#     at once, not hang;
# (3) the probe per position with the int8 control on six seeds (scripts/chip_calls/pr41_probe.py): the
#     program's and the control's probe_mse / probe_diff at each margin, from which the limits are set.
out=$PWD/chiprun_out/pr43/${TAG:-a1}; mkdir -p "$out"
cell=k-exaone-ep8.longctx-saturated
seed=${SEED:-2147485301}
t=$SECONDS
timeout 900 python3 benchmark/run.py --workload $cell --seed $seed --seconds 51 --trace 1 > "$out/run.out" 2> "$out/run.err"
echo "run.py $cell rc=$? after $((SECONDS - t)) s: $(tail -1 "$out/run.out" | cut -c1-6000)"
grep -a "gap mode\|per_layer\|end_to_end\|compil\|strateg\|correct\|compared\|loaded\|window \|^region token\|profiler" \
  "$out/run.out" "$out/run.err" | grep -v "request" | head -90 | cut -c1-300
tail -14 "$out/run.err" | cut -c1-300
cp BENCHMARK.json _checkout/parent/ && cp -r benchmark _checkout/parent/ && cp -r tests/benchmark _checkout/parent/tests/
( cd _checkout/parent && t=$SECONDS && timeout 300 python3 benchmark/run.py --workload $cell --seed $seed --seconds 51 \
    --trace 0 > "$out/parent.out" 2> "$out/parent.err"
  echo "PARENT on $cell rc=$? after $((SECONDS - t)) s: $(tail -2 "$out/parent.err" | cut -c1-300 | tr '\n' ' ')" )
if [ $SECONDS -lt 900 ]; then
  timeout $((1400 - SECONDS)) python3 scripts/chip_calls/pr41_probe.py --workload $cell \
    --seeds ${SEEDS:-2147485311,2147485312,2147485313,2147485314,2147485315,2147485316} \
    --out chiprun_out/pr43/${TAG:-a1}_probe > "$out/probe.out" 2>&1
  echo "probe rc=$? at $SECONDS s"; grep -a "probe \|Error\|error" "$out/probe.out" | cut -c1-900
fi
echo "done at $SECONDS s"
