#!/bin/bash
# PR 37 (no chip): bash benchmark/chip_calls/pr37_hlo_cmp.sh [parent checkout] [output directory] [<config>:<prompt> ...]
# Every program the accepted cells compile (15: qwen25-3b 5, pangu-ultra-moe-ep16 4, mimo-v2-flash-ep16 6), compiled
# for the described v5e:2x2 from the parent's tree (`git archive c263463` at _checkout/parent) and from this one, and
# compared byte for byte: this PR edits flash_attention_prefill, sharded_kernel_call, attention_select, attention_block's
# flat kernel call, the wrapper's pinned layouts and the engine's collect, all of which the old cells run.
parent=${1:-_checkout/parent}; out=${2:-$PWD/chiprun_out/pr37/hlo}; shift 2 2>/dev/null
[ $# -eq 0 ] && set -- qwen25-3b:2048 pangu-ultra-moe-ep16:1024 mimo-v2-flash-ep16:4096
mkdir -p "$out"
python3 scripts/chip_calls/pr32_cells_hlo.py "$parent" "$out/parent" "$@" 2> "$out/parent.err" | sed 's/^/parent: /'
python3 scripts/chip_calls/pr32_cells_hlo.py . "$out/change" "$@" 2> "$out/change.err" | sed 's/^/change: /'
same=0; differ=0
for f in "$out"/parent/*.txt; do
  if cmp -s "$f" "$out/change/$(basename "$f")"; then same=$((same + 1)); echo "same    $(basename "$f")"
  else differ=$((differ + 1)); echo "DIFFERS $(basename "$f")"; fi
done
echo "programs compared: $((same + differ)); byte-identical: $same; differing: $differ"
[ $differ -eq 0 ]
