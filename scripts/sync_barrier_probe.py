#!/usr/bin/env python
"""Is ``jax.block_until_ready`` a sound completion barrier for the donated,
device-resident decode chain? (bench.py's timing rests on the answer.)

Chains N decode steps on the bench app (each step's cache is the donated
output of the one before), then times three things apart: the dispatch of
the chain, ``block_until_ready`` on the last step's tokens, and a host fetch
of those tokens afterwards. The barrier is sound when the fetch that follows
it finds nothing left to wait for (fetch_after_block_ms stays at transfer
cost whatever N is) and the blocked time grows with N like the work does.

Needs the chip; prints one JSON line that names its device.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402
from _bench import build_random_app  # noqa: E402


def main():
    bench.require_tpu()
    import jax

    from nxdi_tpu.runtime.model_wrapper import TAG_TOKEN_GENERATION

    seq_len = 2048
    app, _, _, _ = build_random_app(batch=32, seq_len=seq_len, skip_warmup=False)
    w = app.models[TAG_TOKEN_GENERATION]
    out = app._probe_first_out
    nxt = out["next_inputs"]
    for _ in range(20):  # warm: compile + settle the cache layout
        out, app.kv_cache = w.forward_device(app.params, app.kv_cache, nxt, seq_len)
        nxt = out["next_inputs"]
    np.asarray(out["tokens"])

    rows = []
    for n in (20, 200, 20, 200):
        t0 = time.perf_counter()
        for _ in range(n):
            out, app.kv_cache = w.forward_device(app.params, app.kv_cache, nxt, seq_len)
            nxt = out["next_inputs"]
        t1 = time.perf_counter()
        jax.block_until_ready(out["tokens"])
        t2 = time.perf_counter()
        np.asarray(out["tokens"])
        t3 = time.perf_counter()
        rows.append({
            "steps": n,
            "dispatch_ms": (t1 - t0) * 1e3,
            "block_until_ready_ms": (t2 - t1) * 1e3,
            "fetch_after_block_ms": (t3 - t2) * 1e3,
            "per_step_ms_at_block": (t2 - t0) * 1e3 / n,
            "per_step_ms_at_fetch": (t3 - t0) * 1e3 / n,
        })
    print(json.dumps({
        "probe": "block_until_ready_on_donated_decode_chain",
        "rows": rows,
        "device": bench.device_record(),
    }))


if __name__ == "__main__":
    main()
