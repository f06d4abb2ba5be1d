#!/usr/bin/env python3
"""Run a cell's sets of runs the way a check does, and read their spread:

    python3 benchmark/sets.py --workload <cell> --seeds 11,12,13,14,15,2147483659 \\
        [--sets 2] [--trace 0] [--out chiprun_out/sets]

Each run is ``BENCHMARK.json``'s own command in a process of its own, at the
manifest's ``run_seconds``, one after another; every set uses the same seeds.
Each run's output is kept under ``--out``. The table gives, per metric and
set, the median and the spread (distance between the first and third quartile
of ``statistics.quantiles(values, n=4)`` over the median), the widest spread,
five times it (what a bound is set from) and how far the second set's median
lies from the first's. Every metric a run prints is read, end-to-end or not,
so a candidate can be judged before it is given a bound. This process never
touches JAX: the chip is the run's. Not part of a check.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRIC_LINE = re.compile(r"\] +(?:end_to_end|per_layer) (\S+) = (\S+) ")
COMPARED_LINE = re.compile(r"\] +compared (\S+) = (\S+) \(limit")
MODE_LINE = re.compile(r"\] gap mode (\S+): \d+ gaps = (\S+) % of all \(with the modes above it (\S+) %\)")


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def read_run(stdout: str) -> dict:
    """``{"line": the last line's object or None, "metrics": {name: value}}``
    from one run's standard output (the metrics from its progress lines)."""
    lines = stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (IndexError, ValueError):
        last = None
    found = {m.group(1): float(m.group(2)) for ln in lines for m in [METRIC_LINE.search(ln)] if m}
    for ln in lines:  # what decided ``correct``, and the gap tail's modes, as further rows
        m = COMPARED_LINE.search(ln)
        if m and m.group(2) != "None":
            found[f"compared.{m.group(1)}"] = float(m.group(2))
        m = MODE_LINE.search(ln)
        if m:
            found[f"gap_mode.{m.group(1)}.share_pct"] = float(m.group(2))
            found[f"gap_mode.{m.group(1)}.with_above_pct"] = float(m.group(3))
    return {"line": last, "metrics": found}


def table(sets: list) -> list:
    """Text rows for ``sets`` = one list of ``read_run`` results per set."""
    out = []
    for k, runs in enumerate(sets, 1):
        lines = [r["line"] or {} for r in runs]
        out.append(f"set {k}: {len(runs)} runs; correct {[l.get('correct') for l in lines]}; "
                   f"attempted {[l.get('attempted') for l in lines]}; failed {[l.get('failed') for l in lines]}")
    for name in sorted({n for runs in sets for r in runs for n in r["metrics"]}):
        cols, meds, spreads = [], [], []
        for runs in sets:
            vals = [r["metrics"][name] for r in runs if name in r["metrics"]]
            if len(vals) < 2 or not statistics.median(vals):
                continue
            meds.append(statistics.median(vals))
            spreads.append(spread(vals))
            cols.append(f"median {meds[-1]:.4f} spread {100 * spreads[-1]:.3f}% "
                        f"[{', '.join(f'{v:.3f}' for v in vals)}]")
        if not cols:
            continue
        tail = f" | widest {100 * max(spreads):.3f}% x5 = {500 * max(spreads):.2f}%"
        if len(meds) == 2:
            tail += f"; set 2 / set 1 {100 * (meds[1] / meds[0] - 1):+.2f}%"
        out.append(f"{name:28s} " + "  ".join(cols) + tail)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated; the same in every set")
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", default=os.path.join("chiprun_out", "sets"))
    args = p.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    out_dir = os.path.join(ROOT, args.out)
    os.makedirs(out_dir, exist_ok=True)
    sets = []
    for k in range(1, args.sets + 1):
        runs = []
        for seed in args.seeds.split(","):
            cmd = manifest["command"] + ["--workload", args.workload, "--seed", seed, "--seconds",
                                         str(manifest["run_seconds"]), "--trace", str(args.trace)]
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            stem = os.path.join(out_dir, f"{args.workload}_t{args.trace}_set{k}_seed{seed}")
            for ext, text in ((".out", proc.stdout), (".err", proc.stderr)):
                with open(stem + ext, "w") as f:
                    f.write(text)
            runs.append(read_run(proc.stdout))
            print(f"set {k} seed {seed} rc {proc.returncode}: {proc.stdout.strip().splitlines()[-1:]}", flush=True)
        sets.append(runs)
        print("\n".join(table(sets)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
