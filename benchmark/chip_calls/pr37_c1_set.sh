#!/bin/bash
# PR 37, call c1 (one chip): chiprun --chips 1 --timeout 3500 -- bash benchmark/chip_calls/pr37_c1_set.sh
# one set of six runs of the new cell as a check makes them (sets.py: the manifest's own command, a process a run),
# each seed its own: the spread of every metric against half its bound, every number compared, the gap modes
python3 benchmark/sets.py --workload minicpm-sala-l12.longreason-saturated --sets 1 --trace ${TRACE:-0} \
  --seeds ${SEEDS:-2147484521,2147484522,2147484523,2147484524,2147484525,2147484526} \
  --out chiprun_out/pr37/${TAG:-c1} 2>&1 | cut -c1-1500
