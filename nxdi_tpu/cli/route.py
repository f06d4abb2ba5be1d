"""``python -m nxdi_tpu.cli.route`` — the replica router's operator
surface.

Stands a :class:`~nxdi_tpu.router.frontend.Router` over N replica targets
(each a ``name,metrics_url,ingest_url`` triple — every ``cli.serve
--serve --ingest-port`` process exposes both ports) and either serves the
frontend or runs the scripted routed demo.

Modes:

- ``--demo N --once`` (the tier-1 router smoke): spin up N in-process
  tiny-llama replicas (engines + ingests on ephemeral ports), route a
  short multi-session workload through the frontend **over real localhost
  HTTP**, exercise one cooperative drain, and exit non-zero on ANY
  dispatch or failover error — a request finishing with reason "error", a
  rejected submit, or an unexpected failover all fail the smoke.
- ``--serve``: keep the frontend up (``/submit``, ``/stream``,
  ``/drain``, ``/healthz``, ``/snapshot``, ``/metrics``) over the given
  targets until interrupted.
- ``--once`` with targets: one poll round + the ranked table with the
  router-dispatch column, exit 1 on unreachable replicas.

Usage:

  python -m nxdi_tpu.cli.route --demo 2 --once
  python -m nxdi_tpu.cli.route \\
      r0,http://h1:9400,http://h1:9401 r1,http://h2:9400,http://h2:9401 \\
      --serve --port 9600
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time
from typing import List, Optional

from nxdi_tpu.cli import add_on_cpu_flag, use_cpu_backend
from nxdi_tpu.runtime.faults import jittered_backoff


def setup_route_parser(p: argparse.ArgumentParser) -> None:
    add_on_cpu_flag(p)
    p.add_argument("targets", nargs="*",
                   help="replica targets: name,metrics_url,ingest_url")
    p.add_argument("--demo", type=int, default=0, metavar="N",
                   help="spin up N in-process tiny reference replicas "
                        "(engine + ingest on ephemeral ports) and route a "
                        "demo workload through them")
    p.add_argument("--once", action="store_true",
                   help="run one round (the demo workload, or one poll) "
                        "and exit; non-zero on dispatch/failover errors")
    p.add_argument("--serve", action="store_true",
                   help="keep the router frontend serving until interrupted")
    p.add_argument("--requests", type=int, default=6,
                   help="demo workload size (default 6)")
    p.add_argument("--max-new-tokens", type=int, default=4)
    p.add_argument("--sessions", type=int, default=2,
                   help="demo conversations: requests cycle session ids so "
                        "affinity is exercised (default 2)")
    p.add_argument("--drain-demo", type=int, choices=[0, 1], default=1,
                   help="exercise one cooperative drain mid-demo when >1 "
                        "replica (default 1)")
    p.add_argument("--shed-queue-depth", type=float, default=64.0,
                   help="router load-shedding watermark "
                        "(RouterConfig.shed_queue_depth)")
    p.add_argument("--shed-class-factors", default=None, metavar="JSON",
                   help="per-priority-class multipliers on the shed "
                        "watermark (RouterConfig.shed_class_factors), e.g. "
                        "'{\"interactive\": 2.0, \"best_effort\": 0.5}' — "
                        "best_effort sheds first, interactive last")
    p.add_argument("--degraded-penalty", type=float, default=4.0)
    p.add_argument("--poll-interval", type=float, default=0.5,
                   help="background health/load poll cadence seconds")
    p.add_argument("--timeout", type=float, default=2.0,
                   help="per-replica fleet poll timeout seconds")
    p.add_argument("--staleness", type=float, default=10.0)
    p.add_argument("--unreachable-after", type=int, default=3)
    p.add_argument("--step-delay", type=float, default=0.0, metavar="S",
                   help="demo ingest throttle: sleep S seconds between "
                        "engine steps (makes drains/kills observable "
                        "mid-stream)")
    p.add_argument("--format", choices=["table", "json"], default="table")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9600,
                   help="frontend port (--serve; 0 = ephemeral)")
    p.add_argument("-q", "--quiet", action="store_true")


def _note(quiet: bool, msg: str) -> None:
    if not quiet:
        print(msg, file=sys.stderr, flush=True)


def build_demo_replicas(n: int, quiet: bool, step_delay_s: float = 0.0):
    """N in-process tiny-llama replicas, each with an engine, a started
    ingest, and BOTH ports (metrics + ingest) on ephemeral binds. Returns
    ``(targets, ingests, servers)``."""
    from nxdi_tpu.cli.metrics import build_loaded_reference_app
    from nxdi_tpu.config import OnDeviceSamplingConfig
    from nxdi_tpu.router import ReplicaIngest
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    targets, ingests, servers = [], [], []
    for i in range(n):
        _note(quiet, f"[route] building demo replica {i} ...")
        app = build_loaded_reference_app(dict(
            tp_degree=1,
            batch_size=1,
            ctx_batch_size=1,
            tkg_batch_size=2,
            dtype="bfloat16",
            skip_warmup=True,
            telemetry={"detail": "basic", "replica_id": f"demo-{i}"},
            is_block_kv_layout=True,
            pa_block_size=8,
            pa_num_blocks=32,
            on_device_sampling_config=OnDeviceSamplingConfig(),
        ))
        engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
        ingest = ReplicaIngest(engine, step_delay_s=step_delay_s)
        mserver = app.telemetry.serve(port=0)
        iserver = ingest.serve(port=0)
        targets.append((f"demo-{i}", mserver.url, iserver.url))
        ingests.append(ingest)
        servers.extend([mserver, iserver])
        _note(quiet, f"[route] demo replica {i}: metrics {mserver.url}, "
                     f"ingest {iserver.url}")
    return targets, ingests, servers


def _http(method: str, url: str, payload: Optional[dict] = None,
          timeout: float = 10.0):
    # ONE request-plane HTTP rule with the Router's own transport
    from nxdi_tpu.router import http_json

    return http_json(method, url, payload, timeout)


def run_demo_workload(router, frontend_url: str, args) -> dict:
    """The routed demo over real HTTP: submit a multi-session workload
    through the frontend, poll every stream to completion, exercise one
    cooperative drain mid-way. Returns the summary dict; ``errors`` lists
    every dispatch/failover fault (the smoke's exit condition)."""
    import numpy as np

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(4, 200, size=int(rng.integers(5, 13))).tolist()
        for _ in range(args.requests)
    ]
    errors: List[str] = []
    failed_submits = set()
    drained = None
    rids = []
    for i in range(args.requests):
        if (args.drain_demo and drained is None and len(router.ingest_urls) > 1
                and i == args.requests // 2):
            # cooperative drain: the busiest target stops accepting; the
            # remaining submissions rebalance onto the survivors
            drained = sorted(router.ingest_urls)[-1]
            status, resp = _http(
                "POST", f"{frontend_url}/drain?replica={drained}"
            )
            _note(args.quiet, f"[route] drained {drained}: {resp}")
        rid = f"demo-req-{i}"
        rids.append(rid)
        status, resp = _http("POST", f"{frontend_url}/submit", {
            "request_id": rid,
            "prompt": prompts[i],
            "session_id": f"sess-{i % max(args.sessions, 1)}",
            "max_new_tokens": args.max_new_tokens,
            # QoS passthrough: tenant + class ride the sampling params end
            # to end (and pick the class-aware shed watermark at the
            # frontend) even on engines with QoS off
            "tenant_id": f"tenant-{i % 2}",
            "priority": ("interactive", "batch", "best_effort")[i % 3],
        })
        if status != 200:
            errors.append(f"submit {rid}: HTTP {status} {resp}")
            failed_submits.add(rid)
            continue
        _note(args.quiet,
              f"[route] {rid} -> {resp.get('replica')} ({resp.get('status')})")

    deadline = time.time() + 60.0
    results = {}
    cursors = {rid: 0 for rid in rids}
    pending = [rid for rid in rids if rid not in failed_submits]
    # jittered backoff between re-poll rounds: rounds that make no token
    # progress grow the sleep (capped), progress resets it — idle polling
    # stops hammering the frontend while active streams stay snappy
    backoff_rng = random.Random(0)
    idle_rounds = 0
    while pending and time.time() < deadline:
        progressed = False
        for rid in list(pending):
            status, resp = _http(
                "GET",
                f"{frontend_url}/stream?request_id={rid}"
                f"&cursor={cursors[rid]}",
            )
            if status != 200:
                errors.append(f"stream {rid}: HTTP {status} {resp}")
                pending.remove(rid)
                continue
            if resp["cursor"] > cursors[rid] or resp["done"]:
                progressed = True
            cursors[rid] = resp["cursor"]
            if resp["done"]:
                results[rid] = resp
                pending.remove(rid)
                if resp["finish_reason"] == "error":
                    errors.append(f"{rid} error-finished: {resp['error']}")
        idle_rounds = 0 if progressed else idle_rounds + 1
        time.sleep(jittered_backoff(
            idle_rounds, base_s=0.01, max_s=0.25, rng=backoff_rng
        ))
    for rid in pending:
        errors.append(f"{rid} never finished (deadline)")

    snap = router.snapshot()
    failovers = sum(
        float(v) for _, v in router.failovers_total.series().items()
    )
    if failovers > 0:
        # nothing died in the demo — any failover is a routing bug
        errors.append(f"unexpected failovers: {failovers:g}")
    return {
        "requests": len(rids),
        "finished": len(results),
        "errors": errors,
        "failovers": failovers,
        "drained": drained,
        "dispatches": snap["_router"]["dispatches"],
        "sessions": snap["_router"]["sessions"],
        "sheds": router.sheds_total.total(),
    }


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m nxdi_tpu.cli.route",
        description="replica router: least-loaded + session-affinity "
                    "dispatch with failover, draining, and load shedding",
    )
    setup_route_parser(parser)
    args = parser.parse_args(argv)

    from nxdi_tpu.config import FleetConfig, RouterConfig
    from nxdi_tpu.router import Router

    ingests, servers = [], []
    targets = list(args.targets)
    if args.demo:
        if args.on_cpu:
            use_cpu_backend()
        demo_targets, ingests, servers = build_demo_replicas(
            args.demo, args.quiet, step_delay_s=args.step_delay
        )
        targets.extend(demo_targets)
    if not targets:
        parser.error("no replica targets (pass name,metrics,ingest or --demo N)")

    router_kwargs = dict(
        shed_queue_depth=args.shed_queue_depth,
        degraded_penalty=args.degraded_penalty,
        poll_interval_s=args.poll_interval,
    )
    if args.shed_class_factors:
        try:
            router_kwargs["shed_class_factors"] = json.loads(
                args.shed_class_factors
            )
        except json.JSONDecodeError:
            parser.error("--shed-class-factors wants a JSON object")
    router = Router(
        targets,
        config=RouterConfig(**router_kwargs),
        fleet_config=FleetConfig(
            poll_interval_s=args.poll_interval,
            timeout_s=args.timeout,
            staleness_s=args.staleness,
            unreachable_failures=args.unreachable_after,
        ),
    )

    try:
        router.poll()
        if args.demo and args.once:
            frontend = router.serve(host=args.host, port=0)
            summary = run_demo_workload(router, frontend.url, args)
            from nxdi_tpu.cli.fleet import (
                print_fleet_table,
                router_dispatch_counts,
            )

            router.poll()
            if args.format == "table":
                print_fleet_table(
                    router.monitor,
                    dispatches=router_dispatch_counts(router),
                )
                print(json.dumps(summary))
            else:
                print(json.dumps({"summary": summary,
                                  "snapshot": router.snapshot()}, indent=2))
            if summary["errors"]:
                for e in summary["errors"]:
                    _note(args.quiet, f"[route] ERROR: {e}")
                return 1
            _note(args.quiet,
                  f"[route] {summary['finished']}/{summary['requests']} "
                  f"requests served, dispatches {summary['dispatches']}, "
                  f"0 failovers")
            return 0
        if args.serve:
            frontend = router.serve(host=args.host, port=args.port)
            _note(args.quiet,
                  f"[route] frontend {frontend.url}/submit (/stream, "
                  "/drain, /healthz, /snapshot, /metrics) — Ctrl-C to stop")
            try:
                while True:
                    time.sleep(3600)
            except KeyboardInterrupt:
                pass
            return 0
        # --once over external targets: one round + the table
        states = router.poll()
        from nxdi_tpu.cli.fleet import print_fleet_table, router_dispatch_counts

        if args.format == "table":
            print_fleet_table(
                router.monitor, dispatches=router_dispatch_counts(router)
            )
        else:
            print(json.dumps(router.snapshot(), indent=2))
        bad = sorted(k for k, v in states.items() if v == "unreachable")
        if bad:
            _note(args.quiet, f"[route] unreachable replicas: {', '.join(bad)}")
            return 1
        return 0
    except KeyboardInterrupt:
        return 0
    finally:
        router.stop()
        for ingest in ingests:
            ingest.stop()
        for server in servers:
            server.shutdown()


if __name__ == "__main__":
    sys.exit(main())
