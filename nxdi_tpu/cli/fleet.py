"""``python -m nxdi_tpu.cli.fleet`` — the fleet observatory's operator
surface.

Points a :class:`~nxdi_tpu.telemetry.fleet.FleetMonitor` at N replica
``/snapshot`` endpoints (every ``cli.serve --serve`` / ``cli.metrics
--serve`` process exposes one) and renders the fleet: a live per-replica
table (state, snapshot age, queue depth, busy slots, KV headroom, SLO
attainment, load score), merged ``nxdi_fleet_*`` Prometheus text / JSON,
the merged multi-replica Perfetto trace, and a ``--serve`` federation
endpoint answering the SAME probe paths as a single replica.

Modes:

- ``--once`` (default): one poll round, print the table (or ``--format
  json/prom``), exit **non-zero when any replica is unreachable** — the
  scriptable fleet smoke (tier-1 runs it against two in-process replicas).
- ``--watch``: poll every ``--poll-interval`` seconds, reprinting the
  table until interrupted.
- ``--serve``: keep polling in the background and serve the federated
  /metrics, /metrics.json, /snapshot, /healthz, /trace.json.
- ``--demo N``: no fleet handy — spin up N in-process tiny-llama replicas
  (the same reference app cli.serve drives), run a short serving burst on
  each, and observe them over real localhost HTTP.

Usage:

  # one table of an existing fleet
  python -m nxdi_tpu.cli.fleet http://10.0.0.1:9400 http://10.0.0.2:9400 --once

  # name the replicas, keep watching
  python -m nxdi_tpu.cli.fleet a=http://h1:9400 b=http://h2:9400 --watch

  # zero-setup demo fleet + federation endpoint
  python -m nxdi_tpu.cli.fleet --demo 2 --serve --port 9500
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional

from nxdi_tpu.cli import add_on_cpu_flag, use_cpu_backend
from nxdi_tpu.telemetry.fleet import UNREACHABLE, FleetMonitor


def setup_fleet_parser(p: argparse.ArgumentParser) -> None:
    add_on_cpu_flag(p)
    p.add_argument("targets", nargs="*",
                   help="replica base URLs (http://host:port), optionally "
                        "named as name=url")
    p.add_argument("--once", action="store_true",
                   help="one poll round, print, exit 1 on unreachable "
                        "replicas (default mode)")
    p.add_argument("--watch", action="store_true",
                   help="poll repeatedly, reprinting the table")
    p.add_argument("--serve", action="store_true",
                   help="serve the federated /metrics, /snapshot, /healthz, "
                        "/trace.json while polling in the background")
    p.add_argument("--demo", type=int, default=0, metavar="N",
                   help="spin up N in-process tiny reference replicas on "
                        "ephemeral ports and observe those")
    p.add_argument("--router", default=None, metavar="URL",
                   help="a router frontend's base URL (cli.route --serve): "
                        "its /snapshot is fetched each round and the table "
                        "gains a per-replica router-dispatch-count column")
    p.add_argument("--autoscale-log", action="store_true",
                   help="fetch /autoscale from --router (or the first "
                        "target URL) and print the autoscaler's bounded "
                        "decision journal, one line per decision, then exit")
    p.add_argument("--poll-interval", type=float, default=1.0,
                   help="seconds between poll rounds (FleetConfig.poll_interval_s)")
    p.add_argument("--timeout", type=float, default=2.0,
                   help="per-replica HTTP timeout seconds")
    p.add_argument("--staleness", type=float, default=10.0,
                   help="snapshot age (vs its own _process.snapshot_unix_s) "
                        "beyond which a poll counts as failed")
    p.add_argument("--unreachable-after", type=int, default=3,
                   help="consecutive failed polls before UNREACHABLE")
    p.add_argument("--format", choices=["table", "json", "prom"],
                   default="table")
    p.add_argument("--json", dest="json_path", default=None,
                   help="also write the fleet JSON snapshot to this file")
    p.add_argument("--perfetto", dest="perfetto_path", default=None,
                   help="write the merged multi-replica Perfetto trace here")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9500,
                   help="federation endpoint port (--serve; 0 = ephemeral)")
    p.add_argument("--demo-requests", type=int, default=4,
                   help="serving burst per demo replica (--demo)")
    p.add_argument("-q", "--quiet", action="store_true")


def _note(quiet: bool, msg: str) -> None:
    if not quiet:
        print(msg, file=sys.stderr, flush=True)


def router_dispatch_counts(source) -> Optional[dict]:
    """``{replica: dispatch_count}`` from a router surface: either a live
    :class:`~nxdi_tpu.router.frontend.Router` (its counter is read
    directly) or a router ``/snapshot`` JSON dict (the ``_router`` summary
    every frontend serves). ``None`` when no router data is present."""
    if source is None:
        return None
    dispatches = getattr(source, "dispatches_total", None)
    if dispatches is not None:  # a live Router object
        return {
            labels[0]: float(v) for labels, v in dispatches.series().items()
        }
    if isinstance(source, dict):
        d = (source.get("_router") or {}).get("dispatches")
        if isinstance(d, dict):
            return {str(k): float(v) for k, v in d.items()}
    return None


def _counter_total(snap: Optional[dict], family: str) -> float:
    """Summed series value of a counter family in a replica snapshot."""
    fam = (snap or {}).get(family)
    if not isinstance(fam, dict):
        return 0.0
    return float(sum(row.get("value", 0.0) for row in fam.get("series") or []))


def handoff_counts(monitor: FleetMonitor) -> dict:
    """``{label: (exports, imports)}`` from each replica's EXISTING
    ``nxdi_handoff_{exports,imports}_total`` counters — the disaggregation
    plane's activity per replica (a prefill replica exports, a decode
    replica imports; a unified replica shows 0/0). The fleet-level
    in-flight handoff count is ``sum(exports) - sum(imports)``: chains
    exported whose decode-side import has not landed yet."""
    out = {}
    for rep in monitor.replicas:
        out[rep.label] = (
            _counter_total(rep.snapshot, "nxdi_handoff_exports_total"),
            _counter_total(rep.snapshot, "nxdi_handoff_imports_total"),
        )
    return out


def print_fleet_table(monitor: FleetMonitor, file=None,
                      dispatches: Optional[dict] = None) -> None:
    """The live table: one row per replica, ranked least-loaded first,
    trailing rows for replicas outside the aggregates. The state column
    reads straight off each :class:`LoadSignal` (same poll round as the
    scores). With ``dispatches`` (a router attached — see
    :func:`router_dispatch_counts`) a per-replica router-dispatch-count
    column is appended. ``kv_used`` is NON-RECLAIMABLE usage: replicas
    running the serving prefix cache count evictable cached blocks as
    free, so a warm cache never ranks a replica as loaded."""
    out = file if file is not None else sys.stdout
    sigs = {s.replica: s for s in monitor.load_signals()}
    now = monitor.wall_clock()
    hoffs = handoff_counts(monitor)
    hdr = (f"{'rank':>4} {'replica':<24} {'state':<12} {'role':<8} "
           f"{'age_s':>7} "
           f"{'queue':>5} {'busy':>5} {'kv_free':>7} {'kv_used':>7} "
           f"{'slo%':>6} {'hoff e/i':>9} {'score':>8}")
    if dispatches is not None:
        hdr += f" {'dispatched':>10}"
    print(hdr, file=out)
    print("-" * len(hdr), file=out)
    ranked = list(sigs)
    for rank, label in enumerate(ranked, start=1):
        s = sigs[label]
        rep = next(r for r in monitor.replicas if r.label == label)
        age = rep.snapshot_age_s(now)
        # pre-stamp replicas report no age (format(None, '>7') would raise)
        age_s = "-" if age is None else f"{age:.1f}"
        exp, imp = hoffs.get(label, (0.0, 0.0))
        row = (
            f"{rank:>4} {label:<24} {s.state:<12} {s.role:<8} "
            f"{age_s:>7} "
            f"{s.queue_depth:>5g} {s.slots_busy:>5g} "
            f"{s.kv_blocks_free:>7g} {s.kv_blocks_used:>7g} "
            f"{s.slo_attainment_pct:>6.1f} "
            f"{f'{exp:g}/{imp:g}':>9} {s.score:>8.4f}"
        )
        if dispatches is not None:
            row += f" {dispatches.get(label, 0):>10g}"
        print(row, file=out)
    for rep in monitor.replicas:
        if rep.label in sigs:
            continue
        row = (
            f"{'-':>4} {rep.label:<24} {rep.state:<12} {'-':<8} "
            f"{'-':>7} {'-':>5} {'-':>5} {'-':>7} {'-':>7} {'-':>6} "
            f"{'-':>9} {'-':>8}"
        )
        if dispatches is not None:
            row += f" {dispatches.get(rep.label, 0):>10g}"
        print(row + f"  {rep.last_error or ''}", file=out)
    inflight = (sum(e for e, _ in hoffs.values())
                - sum(i for _, i in hoffs.values()))
    if any(e or i for e, i in hoffs.values()):
        # chains exported whose decode-side import has not landed yet
        print(f"in-flight handoffs (exports - imports): {inflight:g}",
              file=out)


def build_demo_fleet(n: int, requests: int, quiet: bool):
    """N in-process tiny-llama replicas, each with demo serving traffic and
    a MetricsServer on an ephemeral port. Returns (targets, servers)."""
    from nxdi_tpu.cli.metrics import build_loaded_reference_app, run_paged_demo
    from nxdi_tpu.config import OnDeviceSamplingConfig

    targets, servers = [], []
    for i in range(n):
        _note(quiet, f"[fleet] building demo replica {i} ...")
        app = build_loaded_reference_app(dict(
            tp_degree=1,
            batch_size=1,
            dtype="bfloat16",
            skip_warmup=True,
            telemetry={"detail": "full", "replica_id": f"demo-{i}"},
            is_block_kv_layout=True,
            pa_block_size=8,
            pa_num_blocks=32,
            on_device_sampling_config=OnDeviceSamplingConfig(),
        ))
        run_paged_demo(app, requests, max_new_tokens=4)
        server = app.telemetry.serve(port=0)
        servers.append(server)
        targets.append((f"demo-{i}", server.url))
        _note(quiet, f"[fleet] demo replica {i} at {server.url}")
    return targets, servers


def _fetch_router_dispatches(args) -> Optional[dict]:
    """Dispatch counts from ``--router URL``'s /snapshot; None (column
    absent) without the flag, {} on a fetch failure (column shows zeros
    rather than vanishing mid-watch)."""
    if not args.router:
        return None
    import json as _json
    import urllib.request

    try:
        with urllib.request.urlopen(
            args.router.rstrip("/") + "/snapshot", timeout=args.timeout
        ) as resp:
            return router_dispatch_counts(_json.loads(resp.read())) or {}
    except Exception:  # noqa: BLE001 — the router is an optional adornment
        return {}


def fetch_autoscale_payload(base_url: str, timeout: float = 2.0) -> dict:
    """GET ``<base_url>/autoscale`` — the Autoscaler journal every router
    frontend and fleet federation endpoint serves once an autoscaler is
    attached."""
    import urllib.request

    with urllib.request.urlopen(
        base_url.rstrip("/") + "/autoscale", timeout=timeout
    ) as resp:
        return json.loads(resp.read())


def print_autoscale_log(payload: dict, file=None) -> int:
    """Render the bounded decision ring, oldest first; returns the number
    of decisions printed. A payload carrying ``error`` (no autoscaler
    attached at the source) prints that instead."""
    out = file if file is not None else sys.stdout
    if payload.get("error"):
        print(f"autoscale: {payload['error']}", file=out)
        return 0
    decisions = payload.get("decisions") or []
    known = ("t", "action", "replica", "signal_trend", "reason")
    for d in decisions:
        # AutoscaleDecision.to_dict flattens its extra keys into the row
        tail = "".join(
            f" {k}={v}" for k, v in sorted(d.items()) if k not in known
        )
        print(
            f"t={d['t']:10.3f} {d['action']:<9} "
            f"replica={d.get('replica') or '-':<16} "
            f"trend={d['signal_trend']:7.3f} {d['reason']}{tail}",
            file=out,
        )
    trend = payload.get("signal_trend")
    draining = sorted(payload.get("draining") or ())
    standby = sorted(payload.get("standby") or ())
    print(
        f"{len(decisions)} decisions; trend="
        f"{'-' if trend is None else format(trend, '.3f')}"
        + (f"; draining: {', '.join(draining)}" if draining else "")
        + (f"; standby: {', '.join(standby)}" if standby else ""),
        file=out,
    )
    return len(decisions)


def emit(monitor: FleetMonitor, args) -> None:
    if args.format == "table":
        print_fleet_table(monitor, dispatches=_fetch_router_dispatches(args))
    elif args.format == "json":
        print(json.dumps(monitor.snapshot(), indent=2))
    else:
        print(monitor.prometheus_text(), end="")
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(monitor.snapshot(), f, indent=2)
    if args.perfetto_path:
        with open(args.perfetto_path, "w") as f:
            json.dump(monitor.perfetto_trace(), f)
        _note(args.quiet, f"[fleet] merged Perfetto trace: "
                          f"{args.perfetto_path} (open in ui.perfetto.dev)")


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m nxdi_tpu.cli.fleet",
        description="fleet observatory: poll replica /snapshot endpoints, "
                    "merge metrics, rank load",
    )
    setup_fleet_parser(parser)
    args = parser.parse_args(argv)

    from nxdi_tpu.config import FleetConfig

    servers = []
    targets = list(args.targets)
    if args.autoscale_log:
        # journal-only mode: one fetch, print, scriptable exit status
        base = args.router or (
            targets[0].split("=", 1)[-1] if targets else None
        )
        if not base:
            parser.error("--autoscale-log wants --router URL or a target URL")
        try:
            payload = fetch_autoscale_payload(base, timeout=args.timeout)
        except Exception as exc:  # noqa: BLE001 — report, don't trace
            _note(args.quiet, f"[fleet] autoscale fetch failed: {exc}")
            return 1
        print_autoscale_log(payload)
        return 0
    if args.demo:
        if args.on_cpu:
            use_cpu_backend()
        demo_targets, servers = build_demo_fleet(
            args.demo, args.demo_requests, args.quiet
        )
        targets.extend(demo_targets)
    if not targets:
        parser.error("no replica targets (pass URLs or --demo N)")

    monitor = FleetMonitor(
        targets,
        config=FleetConfig(
            poll_interval_s=args.poll_interval,
            timeout_s=args.timeout,
            staleness_s=args.staleness,
            unreachable_failures=args.unreachable_after,
        ),
    )

    try:
        if args.watch and not args.serve:
            while True:
                monitor.poll()
                emit(monitor, args)
                time.sleep(monitor.config.poll_interval_s)
        if args.serve:
            monitor.poll()
            server = monitor.serve(host=args.host, port=args.port)
            _note(args.quiet,
                  f"[fleet] federation endpoint http://{args.host}:"
                  f"{server.port}/metrics (/metrics.json, /snapshot, "
                  "/healthz, /trace.json) — Ctrl-C to stop")
            emit(monitor, args)
            try:
                while True:
                    time.sleep(monitor.config.poll_interval_s)
                    monitor.poll()
            except KeyboardInterrupt:
                server.shutdown()
            return 0
        # --once (the default): one round, scriptable exit status
        states = monitor.poll()
        emit(monitor, args)
        bad = sorted(
            rep.label for rep in monitor.replicas
            if rep.state == UNREACHABLE or rep.failures > 0
        )
        if bad:
            _note(args.quiet,
                  f"[fleet] unreachable/failing replicas: {', '.join(bad)}")
            return 1
        _note(args.quiet,
              f"[fleet] {len(states)} replicas healthy")
        return 0
    except KeyboardInterrupt:
        return 0
    finally:
        for server in servers:
            server.shutdown()


if __name__ == "__main__":
    sys.exit(main())
