"""``python -m nxdi_tpu.cli.flightrec`` — the serving flight recorder's
manual surface.

Two modes:

- **demo / manual dump** (default): drive the tiny llama reference
  app (the same one ``cli.serve`` uses; ``--on-cpu`` for the CPU backend) through a Poisson serving workload
  with the flight recorder on, print the per-step engine timeline (wall /
  dispatch / host split, admissions, decode rows, preemptions,
  retirements, KV headroom), and optionally write a manual postmortem
  bundle (``--bundle FILE``), trigger-fired bundles (``--out DIR`` + SLO
  targets via ``--slo-ttft-ms`` / ``--slo-tpot-ms``), and the per-slot
  Perfetto Gantt (``--perfetto FILE``).
- **inspect** (``--inspect FILE``): summarize an existing postmortem bundle
  — trigger, breaching request, timeline extent, scheduler state sizes,
  whether history was truncated.

Usage:

  # timeline of a 12-request demo workload
  python -m nxdi_tpu.cli.flightrec --requests 12

  # declare SLOs, capture breach bundles + a manual bundle + the Gantt
  python -m nxdi_tpu.cli.flightrec --slo-ttft-ms 200 --slo-tpot-ms 30 \\
      --out /tmp/postmortems --bundle /tmp/manual.json --perfetto /tmp/t.json

  # read a bundle back
  python -m nxdi_tpu.cli.flightrec --inspect /tmp/postmortems/postmortem_*.json
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

from nxdi_tpu.cli import add_on_cpu_flag, use_cpu_backend

import numpy as np


def setup_flightrec_parser(p: argparse.ArgumentParser) -> None:
    add_on_cpu_flag(p)
    p.add_argument("--requests", type=int, default=8,
                   help="Poisson workload size (default 8)")
    p.add_argument("--rate", type=float, default=30.0,
                   help="mean arrival rate in req/s (default 30)")
    p.add_argument("--max-new-tokens", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--pa-block-size", type=int, default=8)
    p.add_argument("--pa-num-blocks", type=int, default=24)
    p.add_argument("--mixed-dispatch", action="store_true",
                   help="drive the unified mixed prefill+decode engine "
                        "(TpuConfig(mixed_dispatch=True)); the timeline's "
                        "program column shows the per-step packing split "
                        "and efficiency")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="declare a TTFT SLO target (TpuConfig(slo=...)); "
                        "breaches fire postmortem bundles")
    p.add_argument("--slo-tpot-ms", type=float, default=None,
                   help="declare a mean inter-token SLO target")
    p.add_argument("--out", default=None, metavar="DIR",
                   help="postmortem_dir: trigger-fired bundles land here")
    p.add_argument("--bundle", default=None, metavar="FILE",
                   help="write a MANUAL postmortem bundle here after the run")
    p.add_argument("--perfetto", default=None, metavar="FILE",
                   help="write the per-slot engine Gantt (Perfetto JSON)")
    p.add_argument("--last", type=int, default=32,
                   help="print at most the last N step records (default 32)")
    p.add_argument("--format", choices=["table", "json"], default="table")
    p.add_argument("--inspect", default=None, metavar="FILE",
                   help="summarize an existing bundle instead of running")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("-q", "--quiet", action="store_true")


def _note(quiet: bool, msg: str) -> None:
    if not quiet:
        print(msg, file=sys.stderr, flush=True)


def inspect_bundle(path: str) -> int:
    with open(path) as f:
        bundle = json.load(f)
    recs = bundle.get("step_records", [])
    span = bundle.get("request_span")
    sched = bundle.get("scheduler") or {}
    print(f"bundle: {path}")
    detail = bundle.get("detail") or {}
    if bundle.get("trigger") == "numerics":
        # numerics-sentinel bundles (telemetry/sentinel.py): lead with WHAT
        # diverged — the nonfinite program or the replay divergence index —
        # before the generic dump
        kind = detail.get("kind", "?")
        print(f"  trigger:   numerics ({kind})")
        if kind == "logit_nonfinite":
            print(
                f"  program:   {detail.get('submodel')}[{detail.get('bucket')}]"
                f"  rows={detail.get('rows')}  nan={detail.get('nan_count')}"
                f"  inf={detail.get('inf_count')}"
                f"  max|logit|={detail.get('max_abs_logit')}"
            )
        else:
            print(
                f"  request:   id={detail.get('request_id')} diverged at "
                f"generated index {detail.get('divergence_index')} "
                f"(replay argmax {detail.get('expected')} vs streamed "
                f"{detail.get('got')}; preemptions="
                f"{detail.get('preemptions')})"
            )
            summ = detail.get("summary") or {}
            if summ.get("suggested_tol_map"):
                print(f"  tol-map:   suggested {summ['suggested_tol_map']}")
    else:
        print(f"  trigger:   {bundle.get('trigger')}  detail={detail}")
    print(f"  at step:   {bundle.get('step')}")
    if span is not None:
        print(
            f"  request:   id={bundle.get('request_id')} "
            f"tokens_in={span.get('tokens_in')} tokens_out={span.get('tokens_out')} "
            f"ttft_s={span.get('ttft_s')}"
        )
        print(f"  phases:    {[p['name'] for p in span.get('phases', [])]}")
    trace_id = bundle.get("trace_id")
    if trace_id:
        hops = bundle.get("trace_hops") or []
        print(f"  trace:     {trace_id} ({len(hops)} hop spans on this "
              "replica; assemble fleet-wide with cli.trace --trace-id)")
        for h in sorted(hops, key=lambda s: s.get("t_start", 0.0)):
            print(f"    {h.get('hop', '?'):<26} "
                  f"{h.get('duration_s', 0.0) * 1e3:>9.3f} ms  "
                  f"span={h.get('span_id')} parent={h.get('parent_span_id')}")
    print(f"  timeline:  {len(recs)} step records", end="")
    if recs:
        host = sum(r["host_s"] for r in recs)
        disp = sum(r["dispatch_s"] for r in recs)
        print(
            f" (steps {recs[0]['step']}..{recs[-1]['step']}, "
            f"dispatch {disp * 1e3:.1f} ms, host {host * 1e3:.1f} ms)"
        )
    else:
        print()
    print(
        f"  scheduler: {len(sched.get('waiting') or [])} waiting, "
        f"{sum(1 for s in (sched.get('slots') or []) if s)} busy slots, "
        f"kv_blocks_free={sched.get('kv_blocks_free')}"
    )
    dropped = bundle.get("history_dropped", 0)
    if dropped:
        print(f"  WARNING: history truncated ({dropped:g} spans/records dropped "
              "before capture)")
    metrics = bundle.get("metrics") or {}
    pm = metrics.get("nxdi_postmortems_total", {}).get("series", [])
    if pm:
        counts = {s["labels"]["trigger"]: s["value"] for s in pm}
        print(f"  postmortems so far: {counts}")
    return 0


def _phases_text(r: dict) -> str:
    """``schedule=0.05 ... other=0.01``: the step's phases in ms, in the
    order a step enters them, then the time under no phase."""
    from nxdi_tpu.telemetry import PHASES

    phases = r.get("phases") or {}
    parts = [f"{k}={phases[k] * 1e3:.2f}" for k in PHASES if k in phases]
    if "other_s" in r:
        parts.append(f"other={r['other_s'] * 1e3:.2f}")
    return " ".join(parts)


def _print_timeline(records: List[dict], last: int) -> None:
    shown = records[-last:]
    if len(shown) < len(records):
        print(f"... {len(records) - len(shown)} earlier steps elided ...")
    hdr = (f"{'step':>5} {'wall_ms':>8} {'disp_ms':>8} {'host_ms':>8} "
           f"{'adm':>3} {'cached':>9} {'pf':>3} {'dec':>3} {'pre':>3} "
           f"{'ret':>3} {'kv_free':>7} {'queue':>5}  program  "
           "phases_ms (host_ms = wall - fetch)")
    print(hdr)
    print("-" * len(hdr))
    for r in shown:
        dec = r["decode"]
        mixed = r.get("mixed")
        prog = ""
        if mixed is not None:
            # packed mixed dispatch: prefill/decode row split + packing
            # efficiency (real packed tokens over the padded token bucket)
            eff = (100.0 * mixed["packed_tokens"] / mixed["padded_tokens"]
                   if mixed["padded_tokens"] else 0.0)
            prog = (
                f"{mixed['submodel']}[{mixed['bucket']}] "
                f"pf={mixed['prefill_rows']} dec={mixed['decode_rows']} "
                f"pack={mixed['packed_tokens']}/{mixed['padded_tokens']} "
                f"({eff:.0f}%)"
            )
        elif dec is not None:
            prog = f"{dec['submodel']}[steps={dec['steps']}]"
            if r.get("chained"):
                prog += " chained"  # dispatched ahead of the previous collect
            if r.get("overrun_tokens"):
                prog += f" overrun={r['overrun_tokens']}"
            if dec["padding_rows"]:
                prog += f" pad={dec['padding_rows']}"
            toks = dec.get("tokens_emitted")
            if toks:
                # per-token host overhead: the sync-boundary cost the
                # device loop amortizes — one launch retiring N tokens
                # divides the step's host remainder by N
                prog += f" tok={toks} host={r['host_s'] * 1e6 / toks:.0f}us/tok"
        # per-admission prefix-cache reuse: K of N (re)prefill tokens were
        # already KV-resident this step (summed across the step's admits)
        adm = r["admitted"]
        if adm and any("total" in a for a in adm):
            cached = (f"{sum(a.get('cached', 0) for a in adm)}"
                      f"/{sum(a.get('total', 0) for a in adm)}")
        else:
            cached = "-"
        print(
            f"{r['step']:>5} {r['wall_s'] * 1e3:>8.2f} "
            f"{r['dispatch_s'] * 1e3:>8.2f} {r['host_s'] * 1e3:>8.2f} "
            f"{len(adm):>3} {cached:>9} {len(r['prefills']):>3} "
            f"{len(dec['rows']) if dec else 0:>3} "
            f"{len(r['preempted']):>3} {len(r['retired']):>3} "
            f"{r['kv_blocks_free'] if r['kv_blocks_free'] is not None else '-':>7} "
            f"{r['queue_depth']:>5}  {prog}  {_phases_text(r)}"
        )


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m nxdi_tpu.cli.flightrec",
        description="serving flight recorder: per-step engine timeline and "
                    "postmortem bundles on the tiny reference app",
    )
    setup_flightrec_parser(parser)
    args = parser.parse_args(argv)

    if args.inspect is not None:
        return inspect_bundle(args.inspect)

    if args.on_cpu:
        use_cpu_backend()
    from nxdi_tpu.cli.metrics import build_loaded_reference_app
    from nxdi_tpu.config import OnDeviceSamplingConfig

    tpu_kwargs = dict(
        tp_degree=1,
        batch_size=1,
        ctx_batch_size=1,
        tkg_batch_size=args.slots,
        dtype="bfloat16",
        skip_warmup=True,
        telemetry={"detail": "full", "postmortem_dir": args.out},
        is_block_kv_layout=True,
        pa_block_size=args.pa_block_size,
        pa_num_blocks=args.pa_num_blocks,
        on_device_sampling_config=OnDeviceSamplingConfig(),
    )
    if args.mixed_dispatch:
        tpu_kwargs["mixed_dispatch"] = True
    if args.slo_ttft_ms is not None or args.slo_tpot_ms is not None:
        tpu_kwargs["slo"] = {
            "ttft_s": None if args.slo_ttft_ms is None else args.slo_ttft_ms / 1e3,
            "tpot_s": None if args.slo_tpot_ms is None else args.slo_tpot_ms / 1e3,
        }
    _note(args.quiet, "[flightrec] building + loading the reference app ...")
    app = build_loaded_reference_app(tpu_kwargs)

    from nxdi_tpu.serving import (
        InferenceEngine,
        SamplingParams,
        SchedulerConfig,
        drive_arrivals,
        goodput_summary,
    )

    engine = InferenceEngine(
        app, SchedulerConfig(num_slots=args.slots), seed=args.seed
    )
    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=args.requests))
    prompts = [
        rng.integers(4, 200, size=int(rng.integers(5, 13))).tolist()
        for _ in range(args.requests)
    ]
    _note(args.quiet, f"[flightrec] {args.requests} Poisson arrivals at "
                      f"{args.rate} req/s")
    outputs, wall = drive_arrivals(
        engine, arrivals,
        lambda eng, i, arrival_s: eng.add_request(
            prompts[i],
            SamplingParams(max_new_tokens=args.max_new_tokens),
            arrival_s=arrival_s,
        ),
    )
    summary = goodput_summary(outputs, wall, slo=app.tpu_config.slo)
    _note(args.quiet, f"[flightrec] {json.dumps(summary)}")

    fl = engine.flight
    records = [r.to_dict() for r in fl.snapshot_records()]
    if args.format == "json":
        print(json.dumps({"summary": summary, "step_records": records}, indent=2))
    else:
        _print_timeline(records, args.last)
    postmortems = fl.summary()["postmortems"]
    if postmortems:
        _note(args.quiet, f"[flightrec] trigger-fired bundles: {postmortems}")
    if args.bundle:
        bundle = fl.postmortem("manual", detail={"source": "cli.flightrec"})
        with open(args.bundle, "w") as f:
            json.dump(bundle, f, indent=2)
        _note(args.quiet, f"[flightrec] manual bundle: {args.bundle}")
    if args.perfetto:
        app.telemetry.write_perfetto_trace(args.perfetto)
        _note(args.quiet, f"[flightrec] Perfetto per-slot Gantt: "
                          f"{args.perfetto} (open in ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
