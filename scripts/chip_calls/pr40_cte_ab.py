"""A prefill program's time with the weights as held, laid out for the
token-generation program, and laid out with the prefill still taking XLA's
cross-program prefetch: one app of ``<config>``, one process, one chip.

    python3 scripts/chip_calls/pr40_cte_ab.py mimo-v2-flash-ep16 4096

Prints one line a variant: the median wall time of a prefill of the largest
bucket (random ids, every row real) and of a token-generation step, each
after two calls that are not timed, and what each prefill program prefetches
across executions. The last line, ``verdict``, says whether the laid-out
prefill is within 3 % of the held one.
"""
import json
import os
import re
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
pl.chooses_layouts = lambda mesh: False  # load as the parent does: params as held
app = serving_app.build_app(config, [bucket], seed=2147485301)
t = time.perf_counter()
app.load()
print(f"loaded in {time.perf_counter() - t:.1f}s", flush=True)
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
    return statistics.median(times)


def prefetched():
    out = []
    for prog in cte._programs.values():
        text = prog._compiled.as_text()
        out += re.findall(r"copy-start\(%([\w.]+)\), cross_program_prefetch_index", text)
        out.append(f"S1={text.count('S(1)')}")
    return out


res = {}
res["held"] = (timed(cte, cte_batch), timed(tkg, tkg_batch), prefetched())
print("held", res["held"], flush=True)

t = time.perf_counter()
layout = pl.decide(app, pl._abstract(app.params, held=True), pl._abstract(app.kv_cache))
app.params = pl.move(app.params, layout.formats)
print(f"laid out {len(layout.moved)} leaves in {time.perf_counter() - t:.1f}s", flush=True)
res["laid_out"] = (timed(cte, cte_batch), timed(tkg, tkg_batch), prefetched())
print("laid_out", res["laid_out"], flush=True)

cte.pin_params(layout.formats, None)  # the prefill as laid out, with the prefetch
res["laid_out_prefetch"] = (timed(cte, cte_batch), res["laid_out"][1], prefetched())
print("laid_out_prefetch", res["laid_out_prefetch"], flush=True)
ok = res["laid_out"][0] <= 1.03 * res["held"][0]
print(f"verdict {'ok' if ok else 'slower'}: cte held {res['held'][0]:.2f} ms, laid out "
      f"{res['laid_out'][0]:.2f} ms, with the prefetch {res['laid_out_prefetch'][0]:.2f} ms", flush=True)
sys.exit(0 if ok else 1)
