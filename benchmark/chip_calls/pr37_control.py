#!/usr/bin/env python3
"""PR 37: the int8 control's probe numbers on several seeds without serving
anything: the configuration's reference over the seeded bf16 weights and over
the same weights rounded to int8 (``correctness.int8_weights``), at the probe
prompt, judged as ``correctness.check(control=...)`` judges them (the control's
logits in the program's place). A run of the cell takes 6.5 minutes, four of
them the reference over 12-24k served tokens; the control's ``probe_mse`` needs
neither the programs nor a window: a minute a seed.

    python3 benchmark/chip_calls/pr37_control.py --workload <cell> --seeds a,b,c --out chiprun_out/pr37/<tag>.jsonl
"""

from __future__ import annotations

import argparse
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
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import cells, correctness, serving_app
    from nxdi_tpu.parallel.layers import sharding_tree
    from nxdi_tpu.parallel.mesh import mesh_from_config

    cell = cells.resolve(cells.load_manifest(), args.workload)
    bench, vocab = cell.config["benchmark"], cell.config["vocab_size"]
    reference = cells.load_plugin("reference", bench["reference"])
    t0 = time.perf_counter()
    say = lambda text: print(f"[control {time.perf_counter() - t0:7.1f}s] {text}", flush=True)  # noqa: E731
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    for seed in (int(x) for x in args.seeds.split(",")):
        app = serving_app.build_app(cell.config, [256], seed)
        app.mesh = mesh_from_config(app.tpu_config, devices=jax.devices()[: cell.chips])
        params = serving_app.seeded_params(
            app.build_params_struct(), sharding_tree(app.param_specs(), app.mesh), seed)
        prompt = correctness.probe_prompt(seed, vocab)
        ref = correctness.reference_probe(reference, params, cell.config, prompt)
        lower = correctness.int8_weights(params)  # donated: the bf16 weights are gone
        del params
        low = correctness.reference_probe(reference, lower, cell.config, prompt)
        del lower
        sq = ((low - ref) ** 2).mean(axis=-1)
        row = {"seed": seed, "control_probe_mse": float(sq.mean()),
               "control_probe_mse_worst_position": float(sq.max()),
               "control_probe_diff": float(np.abs(low - ref).max()),
               "reference_logit_std": float(ref.std()),
               "fails_probe_mse": bool(sq.mean() > bench["logit_mse_tolerance"]),
               "fails_probe_diff": bool(np.abs(low - ref).max() > bench["logit_tolerance"])}
        say(json.dumps(row))
        with open(os.path.join(ROOT, args.out), "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
