#!/bin/bash
# No chip: bash scripts/chip_calls/pr43_hlo_cmp.sh [parent checkout] [output directory]
# The six programs of mimo-v2-flash-ep16 (token generation and CTE 256 .. 4096), compiled for the described
# v5e:2x2 from the parent's tree (`git archive` of the parent at _checkout/parent) and from this one, the
# kernels lowered through Mosaic (pr38_cells_hlo.py), then compared twice: as written, where moving the ring
# store's commit under the `layers.window` scope changes op_name metadata, and with every `metadata={...}`
# removed, where each program must be the parent's byte for byte (the walk moved to models/window_full.py and
# must compute what it computed). Everything it writes stays inside this checkout or the directory given.
parent=${1:-_checkout/parent}; out=${2:-$PWD/chiprun_out/pr43/hlo}
mkdir -p "$out"
python3 scripts/chip_calls/pr38_cells_hlo.py "$parent" "$out/parent" mimo-v2-flash-ep16:4096 \
  2> "$out/parent.err" | sed 's/^/parent: /'
python3 scripts/chip_calls/pr38_cells_hlo.py . "$out/change" mimo-v2-flash-ep16:4096 \
  2> "$out/change.err" | sed 's/^/change: /'
python3 - "$out" <<'PY'
import os, re, sys
out = sys.argv[1]
meta = re.compile(r",? metadata=\{[^}]*\}")
same = meta_only = differ = 0
for f in sorted(os.listdir(f"{out}/parent")):
    a = open(f"{out}/parent/{f}").read()
    b = open(f"{out}/change/{f}").read()
    if a == b:
        same += 1
        print(f"same               {f}")
    elif meta.sub("", a) == meta.sub("", b):
        meta_only += 1
        moved = sorted(set(re.findall(r'op_name="[^"]*"', b)) - set(re.findall(r'op_name="[^"]*"', a)))
        window = sum("layers.window/kv.write" in op for op in moved)
        print(f"metadata only      {f}: {len(moved)} op_names new, {window} of them layers.window/kv.write")
    else:
        differ += 1
        print(f"DIFFERS            {f}")
print(f"programs compared: {same + meta_only + differ}; identical: {same}; metadata only: {meta_only}; "
      f"differing: {differ}")
sys.exit(1 if differ else 0)
PY
