#!/usr/bin/env python3
"""One process, one cell, one run:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds its chips first and fails without a TPU (no CPU fall-back); builds the
cell's app with weights drawn on the device from ``--seed``; warms the cell's
own programs; drives the cell's traffic through ``InferenceEngine`` for
``--seconds``; then, the window closed and the device's peak memory read, holds
what the window served to the configuration's plain reference
(``correctness.py``); prints progress on earlier lines and, as the LAST line of
standard output, one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (``breakdown`` in a traced run) and, last, ``compared``:
every number that decided ``correct`` beside its limit, which are also the last
lines of standard error. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# the script's own directory must not shadow top-level modules; the repo root
# holds both ``benchmark`` and the program
sys.path[0] = ROOT

EXIT_NO_DEVICE = 3
EXIT_NO_PROGRAM = 4
TRACE_SECONDS = 3.0  # a traced run profiles the last seconds of its window


class Report:
    """This process's own handle on standard output. Descriptor 1 is pointed
    at standard error for everything else (a library's print, a C++ log), so
    only :meth:`say` lines can precede the last line and nothing follows it."""

    def __init__(self):
        sys.stdout.flush()
        self._out = os.fdopen(os.dup(1), "w")
        os.dup2(2, 1)

    def say(self, text: str) -> None:
        self._out.write(f"[bench {time.perf_counter() - T_PROCESS:7.1f}s] {text}\n")
        self._out.flush()

    def finish(self, line: dict) -> None:
        self._out.write(json.dumps(line) + "\n")
        self._out.flush()
        self._out.close()


class Tracer:
    """``jax.profiler`` around the last part of the window, into a fixed
    directory inside the checkout (emptied first)."""

    def __init__(self, directory: str, start_after_s: float):
        self.directory = directory
        self.start_after_s = start_after_s
        self.started = False
        self.start_cost_s = self.stop_cost_s = 0.0
        self.on_start = None

    def start(self) -> None:
        import jax

        if self.on_start is not None:
            self.on_start()
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        t = time.perf_counter()
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.start_cost_s = time.perf_counter() - t
        self.started = True

    def stop(self) -> None:
        import jax

        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_cost_s = time.perf_counter() - t

    def xplane(self):
        found = sorted(glob.glob(os.path.join(self.directory, "plugins/profile/*/*.xplane.pb")))
        return found[-1] if found else None


def counter_values(registry) -> dict:
    """``{"<counter name>|<label values joined by ,>": value}`` of every
    counter series in the program's metrics registry."""
    out = {}
    for metric in registry.metrics():
        if getattr(metric, "kind", None) != "counter":
            continue
        for key, value in metric.series().items():
            out[f"{metric.name}|{','.join(key)}"] = float(value)
    return out


def traced_step_loads(records, served, t_from: float):
    """Rows and live KV tokens of each token-generation dispatch that began
    at or after ``t_from``. A row's cached length at its k-th decode is its
    prompt + k; k is counted over every step record since the engine began."""
    prompt_len = {s.request.request_id: s.prompt_len for s in served}
    decodes = {}
    rows_out, live_out = [], []
    for rec in records:
        if rec.decode is None:
            continue
        live = 0
        for row in rec.decode["rows"]:
            rid = row["request_id"]
            decodes[rid] = decodes.get(rid, 0) + 1
            live += prompt_len.get(rid, 0) + decodes[rid]
        if rec.t_start >= t_from:
            rows_out.append(len(rec.decode["rows"]))
            live_out.append(live)
    return rows_out, live_out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from benchmark import cells

    cell = cells.resolve(cells.load_manifest(), args.workload)
    if importlib.util.find_spec("nxdi_tpu") is None:
        print("benchmark: the program (nxdi_tpu/) is not in this checkout", file=sys.stderr)
        return EXIT_NO_PROGRAM

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}. No CPU fall-back.",
            file=sys.stderr,
        )
        return EXIT_NO_DEVICE
    rep = Report()
    rep.finish(run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, rep.say))
    return 0


def prepare(cell, seed: int, devices, say):
    """Set-up up to the window: the loaded, warmed app and its engine."""
    import jax

    from benchmark import costs, serving_app, traffic_gen
    from nxdi_tpu.runtime.application import enable_persistent_cache
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    bench = cell.config["benchmark"]
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(f"cell {cell.name} seed {seed}; device {device}; jax {jax.__version__}")
    costs.peaks_of(device["kind"])  # an unknown device kind is an error now, not later
    say(f"compile cache: {enable_persistent_cache()}")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((time.perf_counter(), name))
        if name.endswith("backend_compile_duration") else None
    )

    buckets = serving_app.prompt_buckets(traffic_gen.max_prompt_len(cell.traffic))
    t = time.perf_counter()
    app = serving_app.build_app(cell.config, buckets, seed)
    app.load()
    jax.block_until_ready(app.params)
    say(f"app {cell.config_name} loaded and warmed in {time.perf_counter() - t:.1f}s "
        f"(tp={bench['tp']}, {bench['slots']} slots, pool {bench['pa_num_blocks']} blocks, "
        f"prompt buckets {buckets})")
    for label, got in sorted(serving_app.program_strategies(app).items()):
        say(f"  {label}: {','.join(got)}")
    faults = serving_app.strategy_faults(app, bench["attention_strategies"])
    for f in faults:
        say(f"  STRATEGY FAULT: {f}")

    engine = InferenceEngine(app, SchedulerConfig(num_slots=bench["slots"]))
    return SimpleNamespace(
        app=app, engine=engine, device=device, faults=faults, compiles=compiles, buckets=buckets,
    )


def compare(prep, cell, seed: int, finished, devices, say):
    """After the window: the program's probe logits, then the device's peak
    memory, then (the program's cache and engine let go) the reference over
    the probe and over a sample of ``finished``. ``(check result, peak bytes)``."""
    from benchmark import cells, correctness

    bench, vocab = cell.config["benchmark"], cell.config["vocab_size"]
    t = time.perf_counter()
    probe_got = correctness.program_probe(prep.app, correctness.probe_prompt(seed, vocab), vocab)
    stats = [d.memory_stats() or {} for d in devices[: cell.chips]]
    peak = int(max(s.get("peak_bytes_in_use", 0) for s in stats))
    params = prep.app.params
    prep.engine = prep.app.kv_cache = None  # the reference runs beside the weights alone
    checked = correctness.check(
        params, cell.config, cells.load_plugin("reference", bench["reference"]), seed,
        probe_got, correctness.sample_served(finished, seed), say,
        routing_margins=cells.load_plugin("reference", bench["reference"], "routing_margins"),
    )
    say(f"comparison with the reference took {time.perf_counter() - t:.1f}s (after the window, "
        f"outside set-up)")
    return checked, peak


def measure(prep, cell, seed: int, seconds: float, trace: bool, say):
    """One window of the cell's traffic through the prepared engine:
    ``(run records, window result, tracer, compilations inside the window)``."""
    import jax

    from benchmark import cells, drive
    from benchmark.records import RunRecords, fault_of

    bench = cell.config["benchmark"]
    engine, registry = prep.engine, prep.app.telemetry.registry
    generate = cells.load_plugin("generator", cell.traffic["generator"])
    offers = generate(cell.traffic, seed, seconds, cell.config["vocab_size"], bench["slots"])
    say(f"traffic {cell.traffic_name}: {len(offers)} requests drawn")

    snap = {}
    tracer = None
    if trace:
        length = min(TRACE_SECONDS, seconds / 2)
        tracer = Tracer(os.path.join(ROOT, ".bench_trace", cell.name), seconds - length)
        tracer.on_start = lambda: snap.setdefault("host_end", counter_values(registry))

    res = drive.drive(
        engine, offers, cell.traffic, seconds,
        annotate=jax.profiler.TraceAnnotation, tracer=tracer,
        on_open=lambda: snap.setdefault("open", counter_values(registry)),
    )
    snap.setdefault("host_end", counter_values(registry))

    drained = cell.traffic["at_close"] == "drain"
    population = res.served if drained else res.finished_in_window
    for s in population:
        s.fault = fault_of(s, cell.config["vocab_size"])
    in_window = [c for c in prep.compiles if res.t_open <= c[0] <= res.t_close + res.drain_s]

    records = engine.flight.snapshot_records()
    run = RunRecords(
        seconds=seconds, t_open=res.t_open, t_close=res.t_close,
        t_host_end=res.t_host_end, setup_s=res.t_open - T_PROCESS, served=res.served,
        population=population, tokens_in_window=res.tokens_in_window,
        steps=[r for r in records
               if r.t_end is not None and r.t_start >= res.t_open and r.t_end <= res.t_host_end],
        counters={k: v - snap["open"].get(k, 0.0) for k, v in snap["host_end"].items()},
        slots=bench["slots"], pool_blocks=bench["pa_num_blocks"],
        block_size=bench["pa_block_size"], tp=bench["tp"], config=cell.config,
        traffic=cell.traffic, device_kind=prep.device["kind"],
    )
    if tracer is not None:
        run.notes["traced_rows"], run.notes["traced_live_kv_tokens"] = traced_step_loads(
            records, res.served, res.t_host_end
        )
    return run, res, tracer, in_window


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, say) -> dict:
    """The run itself, after the device check: returns the last line's object.
    (The CPU tests call this with a toy cell; ``main`` never runs off a TPU.)"""
    from benchmark import cells, serving_app
    from benchmark.records import median, percentile

    prep = prepare(cell, seed, devices, say)
    run, res, tracer, in_window = measure(prep, cell, seed, seconds, trace, say)
    setup_s, population, flight = run.setup_s, run.population, prep.engine.flight
    failed = [s for s in population if s.fault]

    late = [s.offered - s.due for s in res.served]
    say(f"window {res.t_close - res.t_open:.3f}s (asked {seconds}, trace {int(trace)}), set-up {setup_s:.1f}s, "
        f"drain {res.drain_s:.1f}s; offered {len(res.served)}, finished in window "
        f"{len(res.finished_in_window)}, latency population {len(population)}, failed "
        f"{len(failed)}; tokens in window {res.tokens_in_window}; engine steps in window "
        f"{len(run.steps)}; generator lateness p95 {1e3 * (percentile(late, 95) or 0):.2f} ms, "
        f"max {1e3 * max(late, default=0):.2f} ms; compilations in window {len(in_window)}")
    for s in failed[:5]:
        say(f"  FAILED request {s.index}: {s.fault}")
    if res.ran_dry:
        say("  FAULT: the backlog ran dry inside the window")
    if flight.records_dropped:
        say(f"  FAULT: the flight recorder dropped {flight.records_dropped} records")
    for label, xs in (("ttft", run.metric_of_ok("ttft_s")), ("token gaps", run.token_gaps())):
        ladder = ", ".join(f"p{p:g} {1e3 * (percentile(xs, p) or 0):.2f}"
                           for p in (50, 90, 92.5, 95, 97.5, 98, 98.5, 99, 99.5, 100))
        say(f"samples: {label} {len(xs)} (ms: {ladder})")
    above = 0.0  # a gap tail is a percentile of a few modes: which, and how far inside
    for m in reversed(run.gap_modes(prep.buckets)):
        say(f"gap mode {m['mode']}: {m['gaps']} gaps = {m['share_pct']:.3f} % of all (with the modes "
            f"above it {above + m['share_pct']:.3f} %), {m['lo_ms']:.2f} / {m['median_ms']:.2f} / "
            f"{m['hi_ms']:.2f} ms (least / median / most)")
        above += m["share_pct"]

    device_out = dict(prep.device)
    breakdown = None
    if tracer is not None:
        from benchmark import trace_reduce

        path = tracer.xplane() if tracer.started else None
        say(f"profiler: start {tracer.start_cost_s:.2f}s, stop {tracer.stop_cost_s:.2f}s, file {path}")
        summary = None
        if path:
            t = time.perf_counter()
            planes = trace_reduce.load_xplane(path)
            summary = trace_reduce.reduce_trace(planes, serving_app.program_module_names(prep.app))
            say(f"trace reduced in {time.perf_counter() - t:.1f}s")
        run.trace = summary
        if summary is not None:
            device_out["busy_s"] = summary.busy_s
            device_out["window_s"] = summary.window_s
            breakdown = {"device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps}
            for label, runs in sorted(summary.module_s.items()):
                say(f"  module {label}: {len(runs)} executions, median "
                    f"{1e3 * median(runs):.3f} ms, total {sum(runs):.3f}s")

    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        for entry in getattr(cell, kind):
            name = entry["name"]
            value = setup_s if name == "setup_s" else cells.load_plugin(kind, name)(run)
            if value is None:
                continue
            say(f"  {kind} {name} = {value} {entry['unit']}")
            if (kind == "per_layer") == bool(trace):
                metrics[name] = {"value": float(value), "unit": entry["unit"]}

    # what else a sound run has none of, each a number compared with the limit 0
    none_of = {
        "failed_requests": len(failed), "compilations_in_window": len(in_window),
        "strategy_faults": len(prep.faults), "backlog_ran_dry": int(res.ran_dry),
        "flight_records_dropped": flight.records_dropped,
        "trace_unread": int(trace and run.trace is None),
    }
    checked, device_out["memory_peak_bytes"] = compare(prep, cell, seed, population, devices, say)
    compared = dict(checked["compared"])
    compared.update({name: {"value": value, "limit": 0} for name, value in none_of.items()})
    correct = bool(checked["ok"] and not any(none_of.values()))
    line = {
        "correct": correct,
        "attempted": len(population),
        "failed": len(failed),
        "metrics": metrics,
        "device": device_out,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared  # last in the line, and the last lines of standard error
    for name, c in compared.items():
        print(f"compared {name} = {c['value']} limit {c['limit']}", file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    return line


if __name__ == "__main__":
    sys.exit(main())
