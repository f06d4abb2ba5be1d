#!/bin/bash
# No chip: bash scripts/chip_calls/pr42_hlo_cmp.sh [parent checkout] [output directory]
# Every program of the four configurations whose expert layers (or lack of them) this change must leave as
# they were (23: qwen25-3b 5, pangu-ultra-moe-ep16 4, mimo-v2-flash-ep16 6, minicpm-sala-l12 8), compiled for
# the described v5e:2x2 from the parent's tree (`git archive` of the parent at _checkout/parent) and from this
# one, the kernels lowered through Mosaic (pr38_cells_hlo.py: each kernel's body written as its name and the
# sha256 of its MLIR without locations, the sources' line numbers blanked), and compared byte for byte: the
# change is ops/moe.py's expert_form, which every routed program asks at trace time (pangu and mimo hold 16
# experts, top 8, and must choose as before at every bucket). Its output is kept beside it: pr42_hlo_cmp.out.
# Everything it writes stays inside this checkout (chiprun_out/ is in .gitignore) or in the directory given.
parent=${1:-_checkout/parent}; out=${2:-$PWD/chiprun_out/pr42/hlo}
mkdir -p "$out"
python3 scripts/chip_calls/pr38_cells_hlo.py "$parent" "$out/parent" 2> "$out/parent.err" | sed 's/^/parent: /'
python3 scripts/chip_calls/pr38_cells_hlo.py . "$out/change" 2> "$out/change.err" | sed 's/^/change: /'
same=0; differ=0
for f in "$out"/parent/*.txt; do
  if cmp -s "$f" "$out/change/$(basename "$f")"; then same=$((same + 1)); echo "same    $(basename "$f")"
  else differ=$((differ + 1)); echo "DIFFERS $(basename "$f")"; fi
done
echo "programs compared: $((same + differ)); byte-identical: $same; differing: $differ"
[ $differ -eq 0 ]
