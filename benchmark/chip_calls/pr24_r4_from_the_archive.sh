#!/bin/bash
# PR 24, second session, call 4 (one chip): chiprun --chips 1 --timeout 900 -- bash benchmark/chip_calls/pr24_r4_from_the_archive.sh
# the committed files alone: both cells from `git archive $(git write-tree)` unpacked into _checkout/ (made before the call)
mkdir -p chiprun_out/r4
cd _checkout || exit 1
python3 benchmark/run.py --workload qwen25-3b.decode-saturated --seed 2147483663 --seconds 51 --trace 0 > ../chiprun_out/r4/sat.out 2> ../chiprun_out/r4/sat.err
echo "saturated rc=$?"; tail -1 ../chiprun_out/r4/sat.out | cut -c1-900
python3 benchmark/run.py --workload qwen25-3b.chat-steady --seed 2147483665 --seconds 51 --trace 1 > ../chiprun_out/r4/steady.out 2> ../chiprun_out/r4/steady.err
echo "steady rc=$?"; tail -1 ../chiprun_out/r4/steady.out | cut -c1-3000
grep -E "samples|window |per_layer|end_to_end" ../chiprun_out/r4/sat.out ../chiprun_out/r4/steady.out | cut -c1-330
cd ..; mkdir -p _bare/benchmark && cp BENCHMARK.json _bare/ && cp -r _checkout/benchmark/. _bare/benchmark/ && mkdir -p _bare/tests && cp -r _checkout/tests/benchmark _bare/tests/
(cd _bare && python3 benchmark/run.py --workload qwen25-3b.decode-saturated --seed 1 --seconds 51 --trace 0 > ../chiprun_out/r4/bare.out 2> ../chiprun_out/r4/bare.err; echo "bare directory (BENCHMARK.json + paths only) rc=$? stdout bytes=$(wc -c < ../chiprun_out/r4/bare.out)"; tail -2 ../chiprun_out/r4/bare.err)
