#!/bin/bash
# PR 27, call b2 (one chip): chiprun --chips 1 --timeout 3300 -- bash scripts/chip_calls/pr27_b2_tests_traced_pairs.sh [tag]
# (1) tests/tpu paged kernels on the chip (Mosaic parity of the three paged kernels at layer 1 of 3; stops the call if they fail);
# (2) a traced run of each cell on the change, kept whole (steps.json, look.txt, split.json, line.json);
# (3) parent, change, change, parent with --trace 0 in each cell, the two sides of a pair on one seed
#     (_checkout/parent: `git archive <parent commit>`; the benchmark files are the same on both sides).
out=chiprun_out/pr27/${1:-b2}; mkdir -p $out
NXDI_TPU_HW_TESTS=1 python3 -m pytest tests/tpu/test_mosaic_kernels_r3.py -q -p no:cacheprovider > $out/tests_tpu.log 2>&1
rc=$?; tail -3 $out/tests_tpu.log; [ $rc -ne 0 ] && { grep -E "^E |FAILED|Error" $out/tests_tpu.log | head -30; exit 7; }
for cell in decode-saturated:2147483811 chat-steady:2147483813; do
  name=${cell%%:*}; seed=${cell##*:}
  python3 benchmark/chip_calls/pr26_traced_run.py --workload qwen25-3b.$name --seed $seed --seconds 51 --trace 1 \
    --out $out/traced_$name 2> $out/traced_$name.err
  echo "traced $name rc=$? $(python3 -c "
import json; d=json.load(open('$out/traced_$name/line.json')); m=d['metrics']
print(d['correct'], d['failed'], {k: round(m[k]['value'], 2) for k in ('tkg.device_ms','kernel.paged_decode_ms','engine.step_wall_ms','device.idle_pct') if k in m})")"
done
run() {  # side cell seed tag
  local dir=.; [ "$1" = parent ] && dir=_checkout/parent
  (cd $dir && python3 benchmark/run.py --workload qwen25-3b.$2 --seed $3 --seconds 51 --trace 0) \
    > $out/$4_$1_$2.out 2> $out/$4_$1_$2.err
  echo "$4 $1 $2 seed $3 rc=$? $(tail -1 $out/$4_$1_$2.out | cut -c1-420)"
}
for cell in decode-saturated chat-steady; do
  run parent $cell 2147483821 p1; run change $cell 2147483821 p1
  run change $cell 2147483823 p2; run parent $cell 2147483823 p2
done
