#!/usr/bin/env python3
"""PR 35: where a window's slowest engine steps spend their time. Two of the
first six runs of ``mimo-v2-flash-ep16.longctx-saturated`` had ONE token gap of
1.8 and 3.4 s (every other gap under 0.4 s), which alone moved ``out_tok_s`` by
3.6 and 6.7 %. This runs the cell through the harness's own functions on the
seeds given and prints the slowest steps inside the window with their phases
(``fetch`` = waiting for the device; the others are the host's), their rows
and prefills, and the host's garbage collections by generation and duration.

    python3 benchmark/chip_calls/pr35_e1_slow_steps.py --seeds a,b [--seconds 51] [--gc-freeze 0|1]

Needs the TPU."""

from __future__ import annotations

import argparse
import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[0] = ROOT


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="mimo-v2-flash-ep16.longctx-saturated")
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=51.0)
    p.add_argument("--gc-freeze", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import jax

    from benchmark import cells
    from benchmark import run as bench_run

    cell = cells.resolve(cells.load_manifest(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("pr35_e1_slow_steps: needs the TPU", file=sys.stderr)
        return bench_run.EXIT_NO_DEVICE
    t_process = time.perf_counter()
    say = lambda text: print(f"[slow {time.perf_counter() - t_process:7.1f}s] {text}", flush=True)  # noqa: E731
    collections = []
    started = {}

    def on_gc(phase, info):
        if phase == "start":
            started[info["generation"]] = time.perf_counter()
        else:
            t0 = started.pop(info["generation"], None)
            if t0 is not None:
                collections.append((time.perf_counter() - t_process, info["generation"],
                                    time.perf_counter() - t0, info.get("collected", 0)))

    gc.callbacks.append(on_gc)
    for seed in (int(x) for x in args.seeds.split(",")):
        prep = bench_run.prepare(cell, seed, devices, say)
        if args.gc_freeze:
            gc.collect()
            gc.freeze()
        del collections[:]
        t0 = time.perf_counter() - t_process
        run, res, _, _ = bench_run.measure(prep, cell, seed, args.seconds, False, say)
        steps = sorted(run.steps, key=lambda r: -(r.t_end - r.t_start))
        say(f"seed {seed}: {len(run.steps)} steps in the window, tokens {res.tokens_in_window}; the slowest:")
        for r in steps[:6]:
            say(f"  step {r.step}: wall {1e3 * (r.t_end - r.t_start):.1f} ms, rows "
                f"{len(r.decode['rows']) if r.decode else 0}, chained {r.chained}, prefill tokens "
                f"{[q['tokens'] for q in r.prefills]}, blocks free {r.kv_blocks_free}, phases "
                f"{ {k: round(1e3 * v, 1) for k, v in r.phases.items()} }")
        slow = [c for c in collections if c[2] > 0.05]
        say(f"  garbage collections since loading ({t0:.0f}s): {len(collections)} in all, by generation "
            f"{ {g: sum(1 for c in collections if c[1] == g) for g in (0, 1, 2)} }; over 50 ms: "
            f"{[(round(c[0], 1), c[1], round(c[2], 2), c[3]) for c in slow]}")
        prep.engine = prep.app.kv_cache = prep.app.params = None
        del prep, run, res, steps
        gc.unfreeze() if args.gc_freeze else None
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
