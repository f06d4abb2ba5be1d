#!/usr/bin/env python3
"""PR 37: the probe's numbers alone, on many seeds, the program's and the int8
control's side by side and PER POSITION: the app with its 256 bucket only (the
probe is the CTE[256] program with all-position logits), no window, no served
sample. A seed takes half a minute where a run of the cell takes four.

    python3 benchmark/chip_calls/pr37_probe.py --workload <cell> --seeds a,b,c --out chiprun_out/pr37/<tag>
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[0] = ROOT


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", type=int, default=1)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)

    import numpy as np

    from benchmark import cells, correctness, serving_app
    from nxdi_tpu.parallel.layers import sharding_tree

    cell = cells.resolve(cells.load_manifest(), args.workload)
    bench, vocab = cell.config["benchmark"], cell.config["vocab_size"]
    reference = cells.load_plugin("reference", bench["reference"])
    t0 = time.perf_counter()
    say = lambda text: print(f"[probe {time.perf_counter() - t0:7.1f}s] {text}", flush=True)  # noqa: E731
    out = os.path.join(ROOT, args.out)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    for seed in (int(x) for x in args.seeds.split(",")):
        app = serving_app.build_app(cell.config, [256], seed)
        app.load()
        prompt = correctness.probe_prompt(seed, vocab)
        got = correctness.program_probe(app, prompt, vocab)
        params, app.params = app.params, None
        app.kv_cache = None
        ref = correctness.reference_probe(reference, params, cell.config, prompt)
        sq, diff = ((got - ref) ** 2).mean(axis=-1), np.abs(got - ref).max(axis=-1)
        row = {"seed": seed, "probe_mse": float(sq.mean()), "probe_diff": float(diff.max()),
               "worst_positions": [int(i) for i in np.argsort(-sq)[:4]],
               "their_sq": [float(x) for x in np.sort(sq)[::-1][:4]],
               "mse_past_position_8": float(sq[8:].mean()), "logit_std": float(ref.std())}
        if args.control:
            lower = correctness.int8_weights(params)  # donated
            del params
            low = correctness.reference_probe(reference, lower, cell.config, prompt)
            del lower
            csq = ((low - ref) ** 2).mean(axis=-1)
            row.update(control_probe_mse=float(csq.mean()), control_probe_diff=float(np.abs(low - ref).max()),
                       control_worst_positions=[int(i) for i in np.argsort(-csq)[:4]],
                       control_mse_past_position_8=float(csq[8:].mean()))
            np.savez(f"{out}.{seed}.npz", sq=sq, diff=diff, control_sq=csq)
        say(json.dumps(row))
        with open(out + ".jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")
        params = None
        del app
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
