#!/usr/bin/env python3
"""PR 29: the readings a limit of ``correctness.py`` is set from, in one process:

    python3 benchmark/chip_calls/pr29_control.py --workload <cell> --seeds a,b,c \\
        --seconds 25 [--control 1] --out chiprun_out/pr29/<tag>.jsonl

Per seed: the cell's own app from that seed, a short window of the cell's own
traffic at its own load (long enough to finish the mix's longest request), the
comparison a run makes (the program's reading), and with ``--control 1`` the
control's: the reference over int8 weights put in the program's place at the
same prompts and positions (then the weights drawn again from the seed: two
copies do not fit beside the reference). One JSON line per seed. Needs the TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[0] = ROOT


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=25.0)
    p.add_argument("--control", type=int, choices=(0, 1), default=1)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)

    import jax

    from benchmark import cells, correctness, serving_app
    from benchmark import run as bench_run
    from nxdi_tpu.parallel.layers import sharding_tree

    cell = cells.resolve(cells.load_manifest(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("pr29_control: needs the TPU", file=sys.stderr)
        return bench_run.EXIT_NO_DEVICE
    say = lambda text: print(f"[control] {text}", flush=True)  # noqa: E731
    bench, vocab = cell.config["benchmark"], cell.config["vocab_size"]
    reference = cells.load_plugin("reference", bench["reference"])
    margins = cells.load_plugin("reference", bench["reference"], "routing_margins")
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        prep = bench_run.prepare(cell, seed, devices, say)
        run, res, _, in_window = bench_run.measure(prep, cell, seed, args.seconds, False, say)
        app = prep.app
        prompt = correctness.probe_prompt(seed, vocab)
        got = correctness.program_probe(app, prompt, vocab)
        samples = correctness.sample_served(run.population, seed)
        params, app.params = app.params, None
        prep.engine = app.kv_cache = None
        row = {"workload": cell.name, "seed": seed, "seconds": args.seconds,
               "failed": sum(1 for s in run.population if s.fault), "compiles": len(in_window),
               "program": correctness.check(params, cell.config, reference, seed, got, samples, say,
                                            routing_margins=margins)}
        if args.control:
            lower = correctness.int8_weights(params)  # donated: the bf16 weights are gone
            del params
            control = correctness.control_tokens(reference, lower, cell.config, prompt, samples)
            del lower
            params = serving_app.seeded_params(
                app.build_params_struct(), sharding_tree(app.param_specs(), app.mesh), seed)
            row["control"] = correctness.check(params, cell.config, reference, seed, got, samples,
                                               say, routing_margins=margins, control=control)
        with open(os.path.join(ROOT, args.out), "a") as f:
            f.write(json.dumps(row) + "\n")
        say(json.dumps(row))
        del prep, app, params, run, res, samples
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
