#!/bin/bash
# PR 27, call b3 (one chip): chiprun --chips 1 --timeout 2400 -- bash scripts/chip_calls/pr27_b3_from_the_archive.sh
# the committed files alone, from `git archive $(git write-tree)` unpacked into _checkout/final: the benchmark's own
# command with --trace 1 in each cell (the lines the driver's traced runs will carry), then one more
# parent / change pair with --trace 0 in each cell on fresh seeds (change first this time).
out=$PWD/chiprun_out/pr27/b3; mkdir -p $out
run() {  # dir tag cell seed trace
  (cd $1 && python3 benchmark/run.py --workload qwen25-3b.$3 --seed $4 --seconds 51 --trace $5) > $out/$2_$3.out 2> $out/$2_$3.err
  echo "$2 $3 seed $4 trace $5 rc=$? $(tail -1 $out/$2_$3.out | cut -c1-1300)"
}
run _checkout/final traced decode-saturated 2147483831 1
run _checkout/final traced chat-steady 2147483833 1
for cell in decode-saturated chat-steady; do
  run _checkout/final p3_change $cell 2147483835 0
  run _checkout/parent p3_parent $cell 2147483835 0
done
