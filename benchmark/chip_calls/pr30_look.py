#!/usr/bin/env python3
"""PR 30: runs of a cell through the harness's own functions, kept open to
look inside: where set-up goes (compilations with their durations, the ramp's
step records and their phases), and every number the comparison judges PER
POSITION (``correctness.judge``'s arguments, saved as ``<out>.<seed>.npz``),
from which ``routing_margin`` and the limits beside it are read.

    python3 benchmark/chip_calls/pr30_look.py --workload <cell> --seeds a,b,c --seconds 25 \\
        [--control 1] --out chiprun_out/pr30/<tag>

With ``--control 1`` also the control's numbers (``pr29_control.py``'s: the
reference over int8 weights put in the program's place at the same prompts and
positions), per position, as ``<out>.<seed>.control.npz``. One JSON line per
seed in ``<out>.jsonl``. Needs the TPU."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[0] = ROOT

MARGINS = (0.001, 0.002, 0.003, 0.005, 0.0075, 0.01, 0.02)


def by_margin(kept, say) -> None:
    """What each candidate ``routing_margin`` would read on these positions."""
    for m in MARGINS:
        under = kept["probe_margin"] < m
        mse = kept["probe_sq"][~under].mean() if (~under).any() else None
        for label, value, margin in (("probe", kept["probe_diff"], kept["probe_margin"]),
                                     ("served", kept["gaps"], kept["gap_margin"])):
            under = margin < m
            say(f"margin {m}: {label} undecided {under.mean():.3f}; worst decided "
                f"{value[~under].max() if (~under).any() else None}, worst undecided "
                f"{value[under].max() if under.any() else None}; decided probe mse {mse}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=25.0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import cells, correctness, serving_app
    from benchmark import run as bench_run
    from nxdi_tpu.parallel.layers import sharding_tree

    cell = cells.resolve(cells.load_manifest(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("pr30_look: needs the TPU", file=sys.stderr)
        return bench_run.EXIT_NO_DEVICE
    t_process = time.perf_counter()
    now = lambda: time.perf_counter() - t_process  # noqa: E731
    say = lambda text: print(f"[look {now():7.1f}s] {text}", flush=True)  # noqa: E731
    compiles = []  # (when, seconds) of every backend compilation of this process
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((now(), secs))
        if name.endswith("backend_compile_duration") else None
    )

    def compiled_since(t0):
        took = sorted((round(c[1], 2) for c in compiles if c[0] >= t0), reverse=True)
        return f"{len(took)} compilations, {sum(took):.1f}s in all, the longest {took[:5]}"

    out = os.path.join(ROOT, args.out)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    bench, vocab = cell.config["benchmark"], cell.config["vocab_size"]
    reference = cells.load_plugin("reference", bench["reference"])
    margins = cells.load_plugin("reference", bench["reference"], "routing_margins")

    kept = {}
    judge = correctness.judge

    def keeping(bench_, margin, probe_sq, probe_diff, probe_margin, gaps, gap_margin):
        kept.update(probe_sq=probe_sq, probe_diff=probe_diff, probe_margin=probe_margin,
                    gaps=gaps, gap_margin=gap_margin)
        return judge(bench_, margin, probe_sq, probe_diff, probe_margin, gaps, gap_margin)

    correctness.judge = keeping
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = now()
        prep = bench_run.prepare(cell, seed, devices, say)
        say(f"loading: {compiled_since(t0)}")
        t0 = now()
        run, res, _, in_window = bench_run.measure(prep, cell, seed, args.seconds, False, say)
        say(f"set-up {run.setup_s:.1f}s; after loading and up to the close: {compiled_since(t0)}")
        records = prep.engine.flight.snapshot_records()
        ramp = [r for r in records if r.t_end is not None and r.t_end <= res.t_open]
        say(f"steps before the window opened: {len(ramp)}, wall {sum(r.wall_s for r in ramp):.1f}s")
        for r in sorted(ramp, key=lambda r: -r.wall_s)[:3] + ramp[:2] + ramp[-2:]:
            say(f"  step {r.step}: wall {1e3 * r.wall_s:.1f} ms, rows {len(r.decode['rows']) if r.decode else 0}, "
                f"prefill tokens {[q['tokens'] for q in r.prefills]}, phases "
                f"{ {k: round(1e3 * v, 1) for k, v in r.phases.items()} }")
        inside = [r.wall_s for r in run.steps if r.decode is not None and not r.prefills]
        say(f"decode-only steps in the window: {len(inside)}, median wall {1e3 * float(np.median(inside)):.2f} ms; "
            f"tokens in window {res.tokens_in_window}")

        t0 = now()
        prompt = correctness.probe_prompt(seed, vocab)
        got = correctness.program_probe(prep.app, prompt, vocab)
        say(f"program probe {now() - t0:.1f}s")
        samples = correctness.sample_served(run.population, seed)
        params, prep.app.params = prep.app.params, None
        prep.engine = prep.app.kv_cache = None
        t0 = now()
        checked = correctness.check(params, cell.config, reference, seed, got, samples, say,
                                    routing_margins=margins)
        say(f"comparison {now() - t0:.1f}s ({compiled_since(t0)})")
        np.savez(f"{out}.{seed}.npz", **kept)
        by_margin(kept, say)
        row = {"seed": seed, "seconds": args.seconds, "setup_s": run.setup_s,
               "failed": sum(1 for s in run.population if s.fault), "compiles_in_window": len(in_window),
               "tokens_in_window": res.tokens_in_window, "program": checked["compared"],
               "program_ok": bool(checked["ok"])}
        if args.control:
            lower = correctness.int8_weights(params)  # donated: the bf16 weights are gone
            del params
            control = correctness.control_tokens(reference, lower, cell.config, prompt, samples)
            del lower
            params = serving_app.seeded_params(
                prep.app.build_params_struct(), sharding_tree(prep.app.param_specs(), prep.app.mesh), seed)
            judged = correctness.check(params, cell.config, reference, seed, got, samples, say,
                                       routing_margins=margins, control=control)
            # ``judge``'s own verdict on the control under the file's limits
            row["control"], row["control_ok"] = judged["compared"], bool(judged["ok"])
            np.savez(f"{out}.{seed}.control.npz", **kept)
            by_margin(kept, say)
        with open(out + ".jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")
        say(json.dumps(row))
        del prep, params, run, res, samples, records, ramp
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
