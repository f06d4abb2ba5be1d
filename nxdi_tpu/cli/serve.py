"""``python -m nxdi_tpu.cli.serve`` — continuous-batching engine demo.

Drives the tiny llama reference app (the same one ``cli.lint`` audits and
``cli.metrics`` exports; on the attached backend, ``--on-cpu`` for the CPU)
through the serving engine
(``nxdi_tpu/serving``) under a **Poisson arrival** workload: requests
arrive at ``--rate`` req/s (seeded exponential interarrivals), stream
their tokens through per-request callbacks, and ride the slot scheduler —
admission under the KV-block watermark, batched decode, retirement, and
(by default) one **forced preemption** so the recompute-resume path and
its counter are exercised end to end.

The exported Prometheus text is captured at PEAK occupancy (the step with
the most busy slots + queued requests), so the serving gauges
(``nxdi_serve_queue_depth`` / ``nxdi_serve_slots_busy``) and the
``nxdi_serve_preemptions_total`` counter carry the non-trivial under-load
values a dashboard would scrape mid-run; the JSON snapshot is the final
state (all drained).

Usage:

  python -m nxdi_tpu.cli.serve                       # 8 requests, defaults
  python -m nxdi_tpu.cli.serve --requests 16 --rate 50 --stream
  python -m nxdi_tpu.cli.serve --serve --port 9400   # keep /metrics up
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional

from nxdi_tpu.cli import add_on_cpu_flag, use_cpu_backend

import numpy as np


def setup_serve_parser(p: argparse.ArgumentParser) -> None:
    add_on_cpu_flag(p)
    p.add_argument("--requests", type=int, default=8,
                   help="Poisson workload size (default 8)")
    p.add_argument("--rate", type=float, default=30.0,
                   help="mean arrival rate in req/s (default 30)")
    p.add_argument("--max-new-tokens", type=int, default=8)
    p.add_argument("--sessions", type=int, default=4,
                   help="demo traffic cycles its requests over this many "
                        "session ids (Request.session_id — the router "
                        "tier's affinity key; spans carry it)")
    p.add_argument("--slots", type=int, default=4,
                   help="engine slots = decode batch rows (default 4)")
    p.add_argument("--pa-block-size", type=int, default=8)
    p.add_argument("--pa-num-blocks", type=int, default=24,
                   help="paged-KV pool size (small by default so the "
                        "watermark/preemption machinery is visible)")
    p.add_argument("--watermark-blocks", type=int, default=None)
    p.add_argument("--interleave", choices=["prefill_first", "decode_first"],
                   default="prefill_first")
    p.add_argument("--chunked-prefill", type=int, default=None, metavar="CHUNK",
                   help="enable chunked prefill with this chunk size")
    p.add_argument("--mixed-dispatch", action="store_true",
                   help="unified mixed prefill+decode dispatch "
                        "(TpuConfig(mixed_dispatch=True)): every engine "
                        "step packs prefill chunks and decode rows into "
                        "ONE ragged paged-attention program")
    p.add_argument("--prefix-cache", action="store_true",
                   help="radix prefix cache (serving/prefix_cache): retired "
                        "requests' full KV blocks enter a token-keyed radix "
                        "tree; later admissions fork the longest cached "
                        "prefix and prefill only the tail (LRU eviction "
                        "feeds the pool on demand)")
    p.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                   help="open every demo prompt with the same N-token "
                        "system prefix (the multi-tenant shape the prefix "
                        "cache exists for; pair with --prefix-cache to see "
                        "nxdi_prefix_hits/tokens_saved move)")
    p.add_argument("--force-preempt", type=int, choices=[0, 1], default=1,
                   help="force one recompute preemption if none occurs "
                        "naturally (default 1: the demo must exercise the "
                        "resume path)")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="declare a TTFT SLO target (TpuConfig(slo=...)): "
                        "attainment gauges + breach-triggered postmortems")
    p.add_argument("--slo-tpot-ms", type=float, default=None,
                   help="declare a mean inter-token SLO target")
    p.add_argument("--qos", action="store_true",
                   help="enable the QoS control plane (TpuConfig(qos=...)): "
                        "demo requests cycle tenants + priority classes, "
                        "admission orders by deadline slack, preemption "
                        "spares near-breach requests")
    p.add_argument("--qos-quota", default=None, metavar="REFILL:BURST",
                   help="with --qos, a default per-tenant token-bucket "
                        "quota (tokens/s refill : burst tokens); over-quota "
                        "submits error-finish deterministically (429)")
    p.add_argument("--postmortem-dir", default=None, metavar="DIR",
                   help="where trigger-fired flight-recorder bundles land "
                        "(default: in-memory only)")
    p.add_argument("--sentinel-replay-rate", type=float, default=None,
                   metavar="RATE",
                   help="enable the numerics sentinel "
                        "(TpuConfig(sentinel=...)): in-graph logit-health "
                        "stats + teacher-forced shadow replay of this "
                        "fraction of retired requests + the "
                        "preemption-replay invariant; divergences fire "
                        "'numerics' postmortem bundles")
    p.add_argument("--replica-id", default=None, metavar="ID",
                   help="stable replica identity for the fleet observatory "
                        "(TelemetryConfig(replica_id=...); the 'replica' "
                        "label cli.fleet attaches to this process's series; "
                        "default: hostname:pid)")
    p.add_argument("--fault-plan", default=None, metavar="JSON",
                   help="arm a deterministic fault plan for the workload "
                        "(nxdi_tpu/runtime/faults.py): a JSON object or "
                        "@file path with {'seed': N, 'rules': [{'site', "
                        "'trigger', 'n'|'p', 'kind', 'limit'}]}; injections "
                        "count into nxdi_fault_injected_total{site} and "
                        "exercise the step-fault recovery machinery")
    p.add_argument("--watchdog", action="store_true",
                   help="enable the dispatch watchdog "
                        "(TpuConfig(faults={'watchdog': True})): per-program "
                        "timeouts from CostSheet floors x multiplier plus "
                        "bounded transient retry with backoff")
    p.add_argument("--role", choices=["unified", "prefill", "decode"],
                   default="unified",
                   help="serving role (TpuConfig(role=...)): 'prefill' "
                        "compiles CTE + a 1-token TKG and parks finished "
                        "prefills for KV handoff; 'decode' compiles TKG "
                        "only and admits KV imports instead of submits. "
                        "Role replicas skip the local demo workload — pair "
                        "with --serve --ingest-port so a router tier "
                        "drives them")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--stream", action="store_true",
                   help="print each request's tokens as they stream")
    p.add_argument("--format", choices=["prom", "json", "both"], default="both")
    p.add_argument("--json", dest="json_path", default=None,
                   help="write the final JSON telemetry snapshot here")
    p.add_argument("--serve", action="store_true",
                   help="after the workload, serve /metrics until interrupted")
    p.add_argument("--ingest-port", type=int, default=None, metavar="PORT",
                   help="with --serve, also open the replica INGEST on this "
                        "sibling port (nxdi_tpu/router: POST /submit, GET "
                        "/stream, POST /drain) so a router tier can "
                        "dispatch to this process; 0 = ephemeral")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9400)
    p.add_argument("-q", "--quiet", action="store_true")


def _note(quiet: bool, msg: str) -> None:
    if not quiet:
        print(msg, file=sys.stderr, flush=True)


def run_workload(args, app):
    """The Poisson workload over one engine; returns
    ``(engine, outputs, peak_prom, wall_seconds)``."""
    from nxdi_tpu.serving import (
        InferenceEngine,
        SamplingParams,
        SchedulerConfig,
        drive_arrivals,
    )

    engine = InferenceEngine(
        app,
        scheduler_config=SchedulerConfig(
            num_slots=args.slots,
            watermark_blocks=args.watermark_blocks,
            interleave=args.interleave,
            chunk_size=args.chunked_prefill,
            prefix_cache=getattr(args, "prefix_cache", False),
        ),
        seed=args.seed,
    )
    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=args.requests))
    shared = (
        rng.integers(4, 200, size=args.shared_prefix).tolist()
        if getattr(args, "shared_prefix", 0) > 0 else []
    )
    # the compiled window bounds prompt + at least one decode position;
    # keep the shared prefix short enough that per-request tails survive
    limit = engine.window_limit - 1
    shared = shared[: max(0, limit - 4)]
    prompts = [
        (shared + rng.integers(4, 200, size=int(rng.integers(5, 13))).tolist())
        [:limit]
        for _ in range(args.requests)
    ]

    def on_token(req, tok):
        if args.stream:
            print(f"  [req {req.request_id}] +{tok}", file=sys.stderr)

    qos_on = getattr(args, "qos", False)
    if qos_on:
        from nxdi_tpu.ops.sampling import PRIORITY_CLASSES

    def submit(eng, i, arrival_s):
        params = dict(max_new_tokens=args.max_new_tokens)
        if qos_on:
            # the multi-tenant shape: requests cycle tenants and priority
            # classes so every QoS surface (quota, slack, class SLOs) moves
            params["tenant_id"] = f"tenant-{i % 2}"
            params["priority"] = PRIORITY_CLASSES[i % len(PRIORITY_CLASSES)]
        try:
            eng.add_request(
                prompts[i],
                SamplingParams(**params),
                on_token=on_token,
                arrival_s=arrival_s,
                # multi-turn shape: requests cycle over a few conversations
                # so the affinity key is exercised even in this off-router
                # demo
                session_id=f"sess-{i % max(args.sessions, 1)}",
            )
        except ValueError as exc:
            # over-quota rejection (QuotaExceeded rides ValueError) — the
            # deterministic 429 path; the demo reports rather than dies
            if getattr(exc, "status", None) != 429:
                raise
            _note(args.quiet, f"[serve] req {i} rejected: {exc}")

    state = {"forced": args.force_preempt == 0, "peak": None, "peak_load": -1}
    tel = app.telemetry

    def before_step(eng):
        if state["forced"]:
            return
        if (tel is not None and tel.enabled
                and tel.serve_preemptions_total.value() > 0):
            # a NATURAL preemption already exercised the resume path —
            # exactly what --force-preempt promises not to duplicate
            state["forced"] = True
            return
        if eng.scheduler.slots_busy >= 2:
            eng.preempt_youngest()
            state["forced"] = True
            _note(args.quiet, "[serve] forced one recompute preemption")

    def after_step(eng):
        # >=: later ties win, so the peak capture also reflects counters
        # (e.g. the forced preemption) incremented at the same load level
        load = eng.scheduler.slots_busy + eng.scheduler.queue_depth
        if load >= state["peak_load"] and tel is not None and tel.enabled:
            state["peak_load"] = load
            state["peak"] = tel.prometheus_text()

    outputs, wall = drive_arrivals(
        engine, arrivals, submit, before_step=before_step, after_step=after_step
    )
    return engine, outputs, state["peak"], wall


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m nxdi_tpu.cli.serve",
        description="continuous-batching engine demo on the tiny reference app",
    )
    setup_serve_parser(parser)
    args = parser.parse_args(argv)

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
        telemetry={"detail": "full", "postmortem_dir": args.postmortem_dir,
                   "replica_id": args.replica_id},
        is_block_kv_layout=True,
        pa_block_size=args.pa_block_size,
        pa_num_blocks=args.pa_num_blocks,
        on_device_sampling_config=OnDeviceSamplingConfig(),
    )
    if args.slo_ttft_ms is not None or args.slo_tpot_ms is not None:
        tpu_kwargs["slo"] = {
            "ttft_s": None if args.slo_ttft_ms is None else args.slo_ttft_ms / 1e3,
            "tpot_s": None if args.slo_tpot_ms is None else args.slo_tpot_ms / 1e3,
        }
    if args.qos:
        qos: dict = {}
        if args.qos_quota:
            try:
                refill_s, burst_s = args.qos_quota.split(":", 1)
                qos["default_quota"] = {
                    "refill_per_s": float(refill_s), "burst": float(burst_s),
                }
            except ValueError:
                parser.error("--qos-quota wants REFILL:BURST, e.g. 50:200")
        tpu_kwargs["qos"] = qos
    if args.mixed_dispatch:
        tpu_kwargs["mixed_dispatch"] = True
    if args.prefix_cache:
        # compiles the prefix-prefill submodel so cache-hit admissions can
        # start their (re)prefill mid-sequence (mixed dispatch packs
        # arbitrary starts already and needs no extra submodel)
        tpu_kwargs["is_prefix_caching"] = True
    if args.chunked_prefill and not args.mixed_dispatch:
        # under mixed dispatch chunk_size is pure packing policy (the
        # SchedulerConfig above carries it); no prefix-prefill submodel
        tpu_kwargs["chunked_prefill_config"] = {
            "chunk_size": args.chunked_prefill,
            "kernel_q_tile_size": args.chunked_prefill,
        }
    if args.role != "unified":
        # a prefill engine parks every finished prefill for handoff and a
        # decode engine rejects direct submits — the local Poisson demo
        # cannot complete on either, so role replicas build + serve only
        tpu_kwargs["role"] = args.role
        args.requests = 0
        args.force_preempt = 0
    if args.sentinel_replay_rate is not None:
        tpu_kwargs["sentinel"] = {"replay_rate": args.sentinel_replay_rate}
    if args.watchdog:
        tpu_kwargs["faults"] = {"watchdog": True}
    t0 = time.time()
    _note(args.quiet, "[serve] building + loading the reference app ...")
    app = build_loaded_reference_app(tpu_kwargs)
    _note(args.quiet, f"[serve] loaded in {time.time() - t0:.1f}s; "
                      f"{args.requests} Poisson arrivals at {args.rate} req/s")

    from nxdi_tpu.runtime import faults as _faults

    plan = None
    if args.fault_plan:
        spec = args.fault_plan
        if spec.startswith("@"):
            with open(spec[1:]) as f:
                spec = f.read()
        plan = _faults.FaultPlan.from_dict(json.loads(spec))
    if plan is not None:
        with _faults.armed(plan):
            engine, outputs, peak_prom, wall = run_workload(args, app)
        _note(args.quiet,
              f"[serve] fault plan: injected={plan.injected_total()} "
              f"by_site={plan.fired}")
    else:
        engine, outputs, peak_prom, wall = run_workload(args, app)

    from nxdi_tpu.serving import goodput_summary

    for o in sorted(outputs, key=lambda o: o.request_id):
        _note(args.quiet,
              f"[serve] req {o.request_id}: {len(o.token_ids)} tokens, "
              f"{o.finish_reason}, preemptions={o.metrics['preemptions']}")
    # ONE statistics rule with bench.py --serving (serving/workload.py):
    # exact per-request percentiles, SLO fields when targets were declared
    summary = goodput_summary(outputs, wall, slo=app.tpu_config.slo)
    _note(args.quiet, f"[serve] {json.dumps(summary)}")
    if getattr(engine, "qos", None) is not None:
        for cls, row in engine.qos.to_dict()["classes"].items():
            _note(args.quiet,
                  f"[serve] qos[{cls}]: admitted={row['admitted']} "
                  f"rejected={row['rejected_quota']} "
                  f"preempted={row['preempted_deadline']} "
                  f"attainment={row['attainment_pct']}")
    pc = engine.scheduler.prefix_cache
    if pc is not None:
        _note(args.quiet,
              f"[serve] prefix cache: hit_rate={pc.hit_rate_pct:.1f}% "
              f"tokens_saved={pc.tokens_saved_n} cached_blocks={len(pc)} "
              f"evictions={pc.evictions_n} cow_copies={pc.cow_copies_n}")
    if engine.flight is not None and engine.flight.postmortems:
        _note(args.quiet,
              f"[serve] postmortem bundles: {engine.flight.postmortems}")

    tel = app.telemetry
    if args.format in ("prom", "both"):
        # peak-occupancy capture: the under-load gauge values a scrape
        # mid-run would see (final state has everything drained to zero)
        print(peak_prom if peak_prom is not None else tel.prometheus_text(),
              end="")
    if args.format in ("json", "both"):
        print(json.dumps(tel.snapshot(), indent=2))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump({"summary": summary, "telemetry": tel.snapshot()}, f,
                      indent=2)
    if args.serve:
        server = tel.serve(host=args.host, port=args.port)
        _note(args.quiet,
              f"[serve] http://{args.host}:{server.port}/metrics "
              "(/metrics.json, /snapshot, /healthz, /trace.json, "
              "/postmortem) — Ctrl-C to stop")
        ingest = None
        if args.ingest_port is not None:
            # the request plane on the metrics port's sibling: the drained
            # demo engine keeps serving — a router can now dispatch to it
            from nxdi_tpu.router import ReplicaIngest

            ingest = ReplicaIngest(engine)
            iserver = ingest.serve(host=args.host, port=args.ingest_port)
            _note(args.quiet,
                  f"[serve] ingest {iserver.url}/submit "
                  "(/stream, /drain, /status)")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            server.shutdown()
            if ingest is not None:
                ingest.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
