#!/bin/bash
# PR 26, call a3 (one chip): chiprun --chips 1 --timeout 2400 -- bash benchmark/chip_calls/pr26_a3_from_the_archive.sh
# the committed files alone, from `git archive $(git write-tree)` unpacked into _checkout/final:
# a traced run of each cell kept whole (seed 2147483721: the seed of call a2's p1 --trace 0 runs, for what
# tracing costs), then the benchmark's own command: --trace 1 in one cell, --trace 0 in both, on fresh seeds
out=$PWD/chiprun_out/pr26/a3; mkdir -p $out
cd _checkout/final || exit 9
for name in decode-saturated chat-steady; do
  python3 benchmark/chip_calls/pr26_traced_run.py --workload qwen25-3b.$name --seed 2147483721 --seconds 51 --trace 1 \
    --out $out/traced_$name 2> $out/traced_$name.err
  echo "traced $name rc=$?"
done
python3 benchmark/run.py --workload qwen25-3b.chat-steady --seed 2147483731 --seconds 51 --trace 1 > $out/run_t1_chat-steady.out 2> $out/run_t1_chat-steady.err
echo "run.py trace 1 chat-steady rc=$?"; tail -1 $out/run_t1_chat-steady.out | cut -c1-1400
for cell in decode-saturated:2147483733 chat-steady:2147483735; do
  name=${cell%%:*}; seed=${cell##*:}
  python3 benchmark/run.py --workload qwen25-3b.$name --seed $seed --seconds 51 --trace 0 > $out/run_t0_$name.out 2> $out/run_t0_$name.err
  echo "run.py trace 0 $name rc=$? $(tail -1 $out/run_t0_$name.out | cut -c1-400)"
  grep "per_layer sched.schedule_ms\|per_layer dispatch.host_ms\|per_layer engine.host_ms\|per_layer engine.step_wall_ms" $out/run_t0_$name.out | sed 's/^\[bench *[0-9.]*s\]//' | tr '\n' ';'; echo
done
