#!/bin/bash
# PR 32 (no chip): bash scripts/chip_calls/pr32_hlo_cmp.sh [parent checkout] [output directory]
# The nine programs of the benchmark's three cells, compiled for the described v5e:2x2 from the parent's
# tree (`git archive f1e11af` unpacked at _checkout/parent) and from this one, then compared byte for byte.
# Its output as run for PR 32 is kept beside it: pr32_hlo_cmp.out.
# Everything it writes stays inside this checkout (chiprun_out/ is in .gitignore): the HLO texts under
# <output directory>/parent and /change, each compile's stderr beside them as parent.err and change.err.
parent=${1:-_checkout/parent}; out=${2:-$PWD/chiprun_out/pr32/hlo}
mkdir -p "$out"
python3 scripts/chip_calls/pr32_cells_hlo.py "$parent" "$out/parent" 2> "$out/parent.err" | sed 's/^/parent: /'
python3 scripts/chip_calls/pr32_cells_hlo.py . "$out/change" 2> "$out/change.err" | sed 's/^/change: /'
same=0; differ=0
for f in "$out"/parent/*.txt; do
  if cmp -s "$f" "$out/change/$(basename "$f")"; then same=$((same + 1)); echo "same    $(basename "$f")"
  else differ=$((differ + 1)); echo "DIFFERS $(basename "$f")"; fi
done
echo "programs compared: $((same + differ)); byte-identical: $same; differing: $differ"
[ $differ -eq 0 ] && [ $same -eq 9 ]
