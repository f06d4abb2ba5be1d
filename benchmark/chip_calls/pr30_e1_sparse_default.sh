#!/bin/bash
# PR 30, call e1 (one chip): chiprun --chips 1 --timeout 2400 -- bash benchmark/chip_calls/pr30_e1_sparse_default.sh [first seed]
# the new cell on the program's DEFAULT dispatch (sparse; the grouped matmul handed the layer-stacked expert
# weights whole): one traced run that keeps every StepRecord and the device time by instruction
# (pr26_traced_run.py: steps.json, split.json), then the benchmark's own command untraced on another seed.
# AS IT RAN: pr26_traced_run.py made the run and wrote line.json + log.txt, then raised (it reads prep.engine,
# which the harness has let go since PR 29): no steps.json, no split.json; line.json's breakdown has the device ops
out=chiprun_out/pr30/${2:-e1}; mkdir -p $out
cell=pangu-ultra-moe-ep16.reason-saturated
seed=${1:-2147483871}
python3 benchmark/chip_calls/pr26_traced_run.py --workload $cell --seed $seed --seconds 51 --trace 1 --out $out/traced \
  > $out/traced.out 2> $out/traced.err
echo "traced rc=$? $(tail -1 $out/traced.out | cut -c1-1500)"
grep "window \|correctness:\|comparison\|gap mode\|per_layer\|compared\|set-up\|loaded" $out/traced/log.txt | cut -c1-330
python3 - $out/traced/split.json <<'PY'
import json, sys
s = json.load(open(sys.argv[1]))
for k, v in s.items():
    if isinstance(v, dict):
        top = sorted(v.items(), key=lambda kv: -kv[1] if isinstance(kv[1], (int, float)) else 0)[:28]
        print(k, json.dumps(top)[:3000])
    else:
        print(k, v)
PY
python3 benchmark/run.py --workload $cell --seed $((seed + 1)) --seconds 51 --trace 0 > $out/run_t0.out 2> $out/run_t0.err
echo "run.py trace 0 rc=$? $(tail -1 $out/run_t0.out | cut -c1-3500)"
grep "window \|correctness:\|comparison\|gap mode\|samples: token\|loaded" $out/run_t0.out | cut -c1-330
