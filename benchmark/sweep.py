#!/usr/bin/env python3
"""Find a configuration's knee under an open-loop traffic mix, once:

    python3 benchmark/sweep.py --config <name> --traffic <name> --seed <n> \\
        --seconds <s> --rates 4,5,5.5,6

Loads the app once, then runs one window per rate, ascending, in the same
process and prints one row each: every end-to-end metric the manifest has a
reader for, and whether the backlog grew. It stops after two rates in a row
that were not sustained (each such window costs its drain as well).
The rule: a rate is SUSTAINED when no request failed, the mean number of
requests waiting (``StepRecord.queue_depth``) in the window's last quarter is
no higher than in its second quarter plus one request, and the decode slots
are not the buffer instead: at most nine tenths of them busy
(``StepRecord.slots_busy``) on average in the last quarter; and, judged when
the sweep is over, it offers no more output tokens a second than the most any
window of the sweep completed (a short window hides a backlog that slots and
the drain absorb; the overloaded windows show the capacity). The knee is the
highest sustained rate; a steady cell's traffic file carries 0.8 x the knee as
a number. Not part of a check: the driver never runs this. Needs the TPU, like
``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[0] = os.path.dirname(BENCH_DIR)


def sweep(prep, cell, rates, seed: int, seconds: float, say) -> list:
    """One window per rate on the prepared engine; the rows of the table."""
    from benchmark import cells
    from benchmark import run as bench_run

    traffic, slots = cell.traffic, cell.config["benchmark"]["slots"]
    readers = {m["name"]: cells.load_plugin("end_to_end", m["name"])
               for m in cells.load_manifest()["end_to_end"] if m["name"] != "setup_s"}
    rows = []
    for rate in sorted(rates):
        cell.traffic = dict(traffic, rate_per_s=rate)
        run, res, _, in_window = bench_run.measure(prep, cell, seed, seconds, False, say)
        length = res.t_close - res.t_open

        def mean_of(field: str, lo: float, hi: float) -> float:
            q = [getattr(r, field) for r in run.steps
                 if res.t_open + lo * length <= r.t_start < res.t_open + hi * length]
            return sum(q) / len(q) if q else 0.0

        failed = sum(1 for s in run.population if s.fault)
        row = {"rate_per_s": rate, "offered": len(res.served), "failed": failed,
               "offered_tok_s": sum(s.want_new for s in res.served) / seconds,
               "completed_tok_s": res.tokens_in_window / length,
               "waiting_q2": mean_of("queue_depth", 0.25, 0.5),
               "waiting_q4": mean_of("queue_depth", 0.75, 1.0),
               "slots_busy_q4": mean_of("slots_busy", 0.75, 1.0),
               "drain_s": res.drain_s, "compiles_in_window": len(in_window)}
        row.update({n: read(run) for n, read in readers.items()})
        row["sustained"] = (failed == 0 and row["waiting_q4"] <= row["waiting_q2"] + 1.0
                            and row["slots_busy_q4"] <= 0.9 * slots)
        rows.append(row)
        say(json.dumps(row))
        if len(rows) >= 2 and not (rows[-1]["sustained"] or rows[-2]["sustained"]):
            break
    cell.traffic = traffic
    capacity = max(r["completed_tok_s"] for r in rows)
    for r in rows:
        r["sustained"] = r["sustained"] and r["offered_tok_s"] <= capacity
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = p.parse_args(argv)

    import jax

    from benchmark import cells
    from benchmark import run as bench_run

    config = cells.read_json(os.path.join("benchmark", "configs", f"{args.config}.json"))
    traffic = cells.read_json(cells.traffic_path(args.traffic))
    chips = config["benchmark"]["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("sweep: needs the TPU", file=sys.stderr)
        return bench_run.EXIT_NO_DEVICE
    say = lambda text: print(f"[sweep] {text}", flush=True)  # noqa: E731
    cell = cells.Cell(f"{args.config}.{args.traffic}", args.config, config, args.traffic,
                      traffic, chips, [], [])
    prep = bench_run.prepare(cell, args.seed, devices, say)
    rates = [float(r) for r in args.rates.split(",")]
    rows = sweep(prep, cell, rates, args.seed, args.seconds, say)
    print(json.dumps({"device": prep.device, "seconds": args.seconds, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
