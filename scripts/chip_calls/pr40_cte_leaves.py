"""Which laid-out leaves cost ``mimo-v2-flash-ep16``'s prefill its time: one
app, one process, one chip. The weights are laid out as the load-time pass
decides (every moved leaf), then for each subset below only those leaves are
held laid out and every program is pinned to that; a prefill of the largest
bucket (random ids, every row real) and a token-generation step are timed
each time (median wall ms of six calls after two), the prefill without the
cross-program prefetch as the pass compiles it.

    python3 scripts/chip_calls/pr40_cte_leaves.py mimo-v2-flash-ep16 4096
"""
import json
import os
import statistics
import sys
import time

sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]

import jax
import numpy as np

import serving_app
from nxdi_tpu.runtime import model_wrapper as mw
from nxdi_tpu.runtime import param_layout as pl

name, bucket = sys.argv[1], int(sys.argv[2])
config = json.load(open(f"benchmark/configs/{name}.json"))
pl.chooses_layouts = lambda mesh: False  # load with the params as held
app = serving_app.build_app(config, [bucket], seed=2147485311)
app.load()
cte = app.models[mw.TAG_CONTEXT_ENCODING]
tkg = app.models[mw.TAG_TOKEN_GENERATION]
rng = np.random.default_rng(0)
cte_batch = dict(list(cte.warmup_batches())[-1])
cte_batch["input_ids"] = rng.integers(0, 16000, cte_batch["input_ids"].shape, dtype=np.int32)
cte_batch["last_token_index"] = np.full_like(cte_batch["last_token_index"], bucket - 1)
tkg_batch = list(tkg.warmup_batches())[-1]


def timed(wrapper, batch, n=6):
    times = []
    for i in range(n + 2):
        t = time.perf_counter()
        out, app.kv_cache = wrapper.forward(app.params, app.kv_cache, batch)
        jax.block_until_ready(out)
        if i >= 2:
            times.append((time.perf_counter() - t) * 1e3)
    return round(statistics.median(times), 3)


flat, treedef = jax.tree_util.tree_flatten(app.params)
paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(app.params)[0]]
held = [a.format for a in flat]
layout = pl.decide(app, pl._abstract(app.params, held=True), pl._abstract(app.kv_cache))
chosen = jax.tree_util.tree_leaves(layout.formats)
moved = set(layout.moved)
print("moved", sorted(moved), flush=True)
subsets = {
    "all": moved,
    "one_layer_segments": {p for p in moved if "['segments'][1]" not in p},
    "stacked_segment": {p for p in moved if "['segments'][1]" in p},
    "q_only": {p for p in moved if "q_proj" in p},
    "none": set(),
}
for label, keep in subsets.items():
    formats = jax.tree_util.tree_unflatten(
        treedef, [c if p in keep else h for p, c, h in zip(paths, chosen, held)])
    app.params = pl.move(app.params, formats)
    for tag, w in app.models.items():
        w.pin_params(formats, pl._PREFILL_OPTIONS if tag == mw.TAG_CONTEXT_ENCODING else None)
    print(label, len(keep), "cte", timed(cte, cte_batch), "tkg", timed(tkg, tkg_batch), flush=True)
