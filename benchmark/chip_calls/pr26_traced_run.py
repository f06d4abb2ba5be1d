#!/usr/bin/env python3
"""PR 26: one run of a cell exactly as ``benchmark/run.py`` makes it, that also
keeps what the run's last line does not carry (not part of a check):

    python3 benchmark/chip_calls/pr26_traced_run.py --workload <cell> --seed <n> \\
        --seconds 51 --trace 1 --out chiprun_out/pr26/<tag> [--cut-steps 3]

Under ``--out``: ``line.json`` (the run's last line), ``log.txt`` (its progress
lines), ``steps.json`` (every flight StepRecord since the engine began, decode
rows as a count, with the window's ``t_open`` / ``t_host_end`` / ``t_close``),
and in a traced run ``look.txt`` (the trace read by hand: planes, lines, the
program's modules, kernels and spans, with each event's stats), ``split.json``
(idle seconds by phase of the step, device seconds by instruction) and
``cut.json`` (a few engine steps of the trace in the form of
``tests/benchmark/recorded_trace_v5e.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

OP_HEAD = re.compile(r"^(%?[^ ]+) = .*?\s([a-z][a-z0-9\-]*)\(")


def compact_steps(records) -> list:
    out = []
    for r in records:
        d = r.to_dict()
        if d["decode"] is not None:
            d["decode"] = dict(d["decode"], rows=len(d["decode"]["rows"]))
        out.append(d)
    return out


def look(path: str, say) -> None:
    """The trace by hand (on-chip-measurement guide, section 6)."""
    from jax.profiler import ProfileData

    shown_stats = 0
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        say(f"PLANE {plane.name}: {[(ln.name, len(list(ln.events))) for ln in lines][:14]}")
        for line in lines:
            events = list(line.events)
            if line.name == "XLA Modules":
                for name in sorted({e.name for e in events}):
                    say(f"  module {name}")
                for e in events[:2]:
                    say(f"  module event {e.name} dur {e.duration_ns} stats {list(e.stats)}")
            if line.name == "XLA Ops":
                seen = set()
                for e in events:
                    head = e.name.split(" = ")[0]
                    base = re.sub(r"\.\d+$", "", head)
                    want = ("attention" in head or "custom_call" in head or head.startswith("%copy.6")
                            or "kv_commit" in head
                            or "dynamic-update-slice" in head or "dynamic-slice" in head)
                    if want and base not in seen and shown_stats < 40:
                        seen.add(base)
                        shown_stats += 1
                        say(f"  op {e.name[:400]}")
                        say(f"     dur {e.duration_ns} stats {[(k, str(v)[:300]) for k, v in e.stats]}")
            if plane.name == "/host:CPU":
                ours = [e for e in events if e.name.startswith("nxdi.")]
                if ours:
                    say(f"  host line {line.name}: {len(ours)} nxdi.* events of {len(events)}")
                    for e in ours[:12]:
                        say(f"    {e.name} start {e.start_ns} dur {e.duration_ns} stats {list(e.stats)}")


def look_metadata(path: str, say) -> None:
    """What ``ProfileData`` does not show: the stats on an event's METADATA
    (one record per distinct HLO instruction), read from the raw protobuf
    where this installation has its Python module."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:
        say(f"event metadata not read: {e}")
        return
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        names = {i: m.name for i, m in plane.stat_metadata.items()}

        def value(stat):
            kind = stat.WhichOneof("value")
            v = getattr(stat, kind) if kind else None
            return names.get(v, v) if kind == "ref_value" else v

        say(f"RAW {plane.name}: {len(plane.event_metadata)} event metadata records, "
            f"stat names {sorted(set(names.values()))[:60]}")
        shown = 0
        for meta in plane.event_metadata.values():
            head = meta.name.split(" = ")[0]
            if not (head.startswith(("%copy.6", "%paged_attention", "%flash_attention", "%tpu_custom_call"))
                    or "dynamic-update-slice_fusion.4" in head or "dynamic-slice_bitcast_fusion.4" in head
                    or head.startswith("%bitcast_add_fusion.5")):
                continue
            shown += 1
            if shown > 14:
                break
            say(f"  meta {meta.name[:200]} | display {meta.display_name[:120]}")
            for st in meta.stats:
                say(f"      {names.get(st.metadata_id)} = {str(value(st))[:400]}")


def split(planes, say) -> dict:
    from benchmark import program_trace, trace_reduce

    idle = program_trace.idle_by_phase_s(planes)
    chips = sorted(n for n in planes if trace_reduce.DEVICE_PLANE.match(n))
    if not chips:
        return {"idle_by_phase_s": idle}
    first = chips[0]
    ops = [e for e in planes[first].get(trace_reduce.LINE_OPS, []) if e[2] > 0]
    by_op = {}
    for (name, _, _), own in zip(ops, trace_reduce.self_ns(ops)):
        label = trace_reduce.op_label(name)
        by_op[label] = by_op.get(label, 0.0) + own / 1e9
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:25]
    modules = {}
    for name, _, dur in planes[first].get(trace_reduce.LINE_MODULES, []):
        modules.setdefault(trace_reduce.module_base(name), []).append(dur / 1e9)
    out = {
        "idle_by_phase_s": idle,
        "idle_unattributed_pct": program_trace.idle_unattributed_pct(planes),
        "device_ops_self_s": top,
        "modules": {k: {"n": len(v), "total_s": sum(v)} for k, v in modules.items()},
        "kernels_s_per_tkg": {
            k: program_trace.kernel_s_per_execution(planes, k)
            for k in ("paged_attention_decode", "flash_attention_prefill", "paged_attention_prefill")
        },
        "prefill_kernel_s_per_cte": program_trace.kernel_s_per_execution(
            planes, "flash_attention_prefill", "jit_context_encoding_model"),
    }
    say(json.dumps(out, indent=1))
    return out


def cut(planes, steps: int, recorded: str) -> dict:
    """``steps`` engine steps from the middle of the trace, host events under
    5 us dropped, times rebased, each op's HLO text cut to name and opcode."""
    from benchmark import trace_reduce

    host = planes[trace_reduce.HOST_PLANE]
    bench = sorted(
        (e for line in host.values() for e in line if e[0] == "bench.engine_step"),
        key=lambda e: e[1],
    )
    mid = len(bench) // 2
    take = bench[mid:mid + steps]
    t0, t1 = take[0][1], take[-1][1] + take[-1][2]

    def inside(e):
        return e[1] >= t0 and e[1] + e[2] <= t1

    def short(name):
        m = OP_HEAD.match(name)
        return f"{m.group(1)} = _ {m.group(2)}(" if m else name[:120]

    out = {}
    for plane, lines in planes.items():
        if trace_reduce.DEVICE_PLANE.match(plane):
            out[plane] = {
                ln: [[short(e[0]) if ln == trace_reduce.LINE_OPS else e[0], e[1] - t0, e[2]]
                     for e in ev if inside(e)]
                for ln, ev in lines.items() if ln in (trace_reduce.LINE_OPS, trace_reduce.LINE_MODULES)
            }
        elif plane == trace_reduce.HOST_PLANE:
            out[plane] = {
                ln: kept for ln, ev in lines.items()
                if (kept := [[e[0][:120], e[1] - t0, e[2]] for e in ev if inside(e) and e[2] >= 5000])
            }
    return {"recorded": recorded, "planes": out}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    p.add_argument("--out", required=True)
    p.add_argument("--cut-steps", type=int, default=3)
    args = p.parse_args(argv)

    import jax

    from benchmark import cells, program_trace, trace_reduce
    from benchmark import run as bench_run

    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, "log.txt"), "w")

    def say(text):
        log.write(text + "\n")
        log.flush()

    kept = {}
    prepare, measure = bench_run.prepare, bench_run.measure

    def keep_prepare(*a, **k):
        kept["prep"] = prepare(*a, **k)
        return kept["prep"]

    def keep_measure(*a, **k):
        kept["measured"] = measure(*a, **k)
        return kept["measured"]

    bench_run.prepare, bench_run.measure = keep_prepare, keep_measure
    cell = cells.resolve(cells.load_manifest(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return bench_run.EXIT_NO_DEVICE
    line = bench_run.run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, say)
    with open(os.path.join(args.out, "line.json"), "w") as f:
        json.dump(line, f)

    res = kept["measured"][1]
    with open(os.path.join(args.out, "steps.json"), "w") as f:
        json.dump({
            "t_open": res.t_open, "t_host_end": res.t_host_end, "t_close": res.t_close,
            "steps": compact_steps(kept["prep"].engine.flight.snapshot_records()),
        }, f)

    if args.trace:
        path = program_trace.newest_xplane()
        planes = trace_reduce.load_xplane(path)
        with open(os.path.join(args.out, "split.json"), "w") as f:
            json.dump(split(planes, say), f, indent=1)
        recorded = (f"{line['device']['kind']}, jax {jax.__version__}, {args.workload}, PR 26 (the "
                    f"program's nxdi.step spans, kernel and module names); cut to {args.cut_steps} "
                    "engine steps, host events under 5 us dropped, times rebased, the HLO text of "
                    "each op cut to its name and opcode")
        with open(os.path.join(args.out, "cut.json"), "w") as f:
            json.dump(cut(planes, args.cut_steps, recorded), f)
        with open(os.path.join(args.out, "look.txt"), "w") as f:
            look(path, lambda s: f.write(s + "\n"))
            try:
                look_metadata(path, lambda s: f.write(s + "\n"))
            except Exception as e:  # noqa: BLE001 — a reading aid, never the run's fault
                f.write(f"event metadata not read: {e!r}\n")
    print(json.dumps({k: line[k] for k in ("correct", "attempted", "failed", "metrics")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
