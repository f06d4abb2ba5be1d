"""Export surfaces: Perfetto/Chrome ``trace_events`` JSON and a stdlib
HTTP endpoint for Prometheus scrapes and router probes.

The Prometheus text and JSON snapshot formatters live on the registry
(:func:`nxdi_tpu.telemetry.registry.prometheus_text`,
:meth:`~nxdi_tpu.telemetry.registry.MetricsRegistry.snapshot`); this module
holds everything that needs the span tracker, the flight recorder, or a
socket.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

#: pid of the per-request span tracks / the engine-step timeline tracks
REQUEST_PID = 1
ENGINE_PID = 2


def perfetto_trace(
    tracker, process_name: str = "nxdi_tpu", flight=None
) -> dict:
    """Chrome/Perfetto ``trace_events`` JSON of the tracked request spans,
    plus (when a flight recorder is attached) the engine-step timeline.

    Requests render as one track each (``pid`` 1, ``tid`` = request id) of
    complete ("X") phase slices. The flight recorder adds a second process
    (``pid`` 2): **one track per decode slot** carrying the slot's
    prefill / decode / preempted segments per engine step, plus a
    **host-overhead track** whose slices are each step's
    ``wall - dispatch`` remainder — a ``cli.serve`` run opens as a per-slot
    Gantt chart. Timestamps are microseconds relative to the earliest
    event so the trace opens at t=0 in ``ui.perfetto.dev`` /
    ``chrome://tracing``; the file can sit next to an xprof capture of the
    same run (``nxdi_tpu.utils.profiling.trace``).
    """
    spans = list(tracker.spans)
    records = flight.snapshot_records() if flight is not None else []
    starts = [s.t_start for s in spans] + [r.t_start for r in records]
    t0 = min(starts, default=0.0)

    def us(t: float) -> float:
        return round((t - t0) * 1e6, 3)

    def dur_us(seconds: float) -> float:
        return round(max(seconds, 0.0) * 1e6, 3)

    events = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": REQUEST_PID,
            "args": {"name": f"{process_name} requests"},
        }
    ]
    for s in spans:
        events.append({
            "name": "thread_name",
            "ph": "M",
            "pid": REQUEST_PID,
            "tid": s.request_id,
            "args": {"name": f"request {s.request_id}"},
        })
        end = s.t_end if s.t_end is not None else s.t_start
        events.append({
            "name": "request",
            "cat": "request",
            "ph": "X",
            "pid": REQUEST_PID,
            "tid": s.request_id,
            "ts": us(s.t_start),
            "dur": dur_us(end - s.t_start),
            "args": {
                "tokens_in": s.tokens_in,
                "tokens_out": s.tokens_out,
                "ttft_ms": None if s.ttft_s is None else round(s.ttft_s * 1e3, 3),
            },
        })
        for name, b, e in s.phases:
            events.append({
                "name": name,
                "cat": "phase",
                "ph": "X",
                "pid": REQUEST_PID,
                "tid": s.request_id,
                "ts": us(b),
                "dur": dur_us(e - b),
            })

    if flight is not None:
        events.extend(_engine_timeline_events(flight, records, us, dur_us))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _engine_timeline_events(flight, records, us, dur_us) -> list:
    """The engine-step Gantt: slot tracks + the host-overhead track."""
    host_tid = flight.num_slots
    events = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": ENGINE_PID,
            "args": {"name": "engine steps (per slot)"},
        },
        {
            "name": "thread_name",
            "ph": "M",
            "pid": ENGINE_PID,
            "tid": host_tid,
            "args": {"name": "host overhead"},
        },
    ]
    for slot in range(flight.num_slots):
        events.append({
            "name": "thread_name",
            "ph": "M",
            "pid": ENGINE_PID,
            "tid": slot,
            "args": {"name": f"slot {slot}"},
        })

    def slot_slice(name, slot, rec, args):
        return {
            "name": name,
            "cat": "engine",
            "ph": "X",
            "pid": ENGINE_PID,
            "tid": slot,
            "ts": us(rec.t_start),
            "dur": dur_us(rec.wall_s),
            "args": args,
        }

    for rec in records:
        for pf in rec.prefills:
            events.append(slot_slice("prefill", pf["slot"], rec, {
                "request_id": pf["request_id"],
                "submodel": pf["submodel"],
                "start": pf["start"],
                "tokens": pf["tokens"],
            }))
        if rec.decode is not None:
            toks = rec.decode.get("tokens_emitted")
            dec_args = {
                "steps": rec.decode["steps"],
                "padding_rows": rec.decode["padding_rows"],
            }
            if toks:
                # per-token host overhead on the slot track: the launch
                # amortizes the step's host remainder over every real
                # token it retired (the device loop's whole point)
                dec_args["tokens_emitted"] = toks
                dec_args["host_us_per_tok"] = round(
                    rec.host_s * 1e6 / toks, 3
                )
            for row in rec.decode["rows"]:
                events.append(slot_slice("decode", row["slot"], rec, {
                    "request_id": row["request_id"], **dec_args,
                }))
        for pe in rec.preempted:
            events.append(slot_slice("preempted", pe["slot"], rec, {
                "request_id": pe["request_id"],
            }))
        # the step's wall less its wait for the device's tokens (the fetch
        # phase): the host-side sync/orchestration boundary (Kernel
        # Looping's target), split by phase in the arguments
        events.append({
            "name": "host",
            "cat": "engine",
            "ph": "X",
            "pid": ENGINE_PID,
            "tid": host_tid,
            "ts": us(rec.t_start),
            "dur": dur_us(rec.host_s),
            "args": {
                "step": rec.step,
                "wall_ms": round(rec.wall_s * 1e3, 3),
                "dispatch_ms": round(rec.dispatch_s * 1e3, 3),
                "phases_ms": {
                    k: round(v * 1e3, 3) for k, v in rec.phases.items()
                },
                "other_ms": round(rec.other_s * 1e3, 3),
            },
        })
    return events


def write_perfetto_trace(
    tracker, path: str, process_name: str = "nxdi_tpu", flight=None
) -> dict:
    trace = perfetto_trace(tracker, process_name=process_name, flight=flight)
    with open(path, "w") as f:
        json.dump(trace, f)
    return trace


PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def telemetry_routes(tel) -> list:
    """The replica probe surface as ``(path_prefix, content_type, fn)``
    rows, longest-match-first. A ``fn`` returning ``None`` answers 404 (the
    flight recorder may not be attached). Shared with the fleet federation
    endpoint (telemetry/fleet.py), which serves the SAME paths over the
    merged view — a router probe never needs to know which tier it hit."""

    def healthz():
        return json.dumps({
            "status": "ok",
            "replica_id": tel.replica_id,
            "requests_total": tel.requests_total.total(),
            "engine_steps": (
                tel.flight.steps if tel.flight is not None else None
            ),
            "spans_dropped": tel.spans_dropped_total.total(),
        })

    def postmortem():
        if tel.flight is None:
            return None
        return json.dumps(
            tel.flight.postmortem("manual", detail={"source": "http"}),
            indent=2,
        )

    def traces():
        return json.dumps({
            "replica_id": tel.replica_id,
            "spans": tel.trace_spans(),
        })

    return [
        ("/healthz", "application/json", healthz),
        ("/metrics.json", "application/json",
         lambda: json.dumps(tel.snapshot(), indent=2)),
        ("/snapshot", "application/json",
         lambda: json.dumps(tel.snapshot(), indent=2)),
        ("/traces", "application/json", traces),
        ("/trace.json", "application/json",
         lambda: json.dumps(tel.perfetto_trace())),
        ("/postmortem", "application/json", postmortem),
        ("/metrics", PROM_CONTENT_TYPE, tel.prometheus_text),
    ]


class MetricsServer:
    """Tiny stdlib HTTP server on a daemon thread:

    - ``/metrics``       Prometheus text exposition
    - ``/metrics.json``  JSON snapshot
    - ``/snapshot``      alias of ``/metrics.json`` (router-probe surface)
    - ``/healthz``       liveness JSON (router-probe surface)
    - ``/traces``        distributed-trace hop spans (telemetry/tracing.py)
    - ``/trace.json``    Perfetto trace_events
    - ``/postmortem``    manual flight-recorder dump (404 without a
      recorder attached); the bundle is returned AND written to the
      recorder's ``postmortem_dir`` when configured

    A route row is either the classic probe shape ``(prefix, ctype, fn)``
    (GET, ``fn()`` -> body) or the request-plane shape
    ``(method, prefix, ctype, fn)`` where ``fn(path, body)`` receives the
    raw request path (query string included) and the request body bytes
    (``b""`` for GET). Either ``fn`` may return ``str``/``bytes`` (200),
    ``None`` (404), or ``(status, body)`` — the explicit-status form is
    what the replica ingest and the router frontend use for backpressure
    answers (429 shed, 503 draining) that a plain probe route can't
    express. POST is how ``/submit`` and ``/drain`` arrive; matching is
    method-exact, longest-prefix-first by table order as before.

    ``port=0`` binds an OS-assigned ephemeral port; read it back from
    ``.port`` (or ``.url``) — multi-replica tests and local fleets never
    need to coordinate hard-coded ports. ``shutdown()`` is graceful and
    idempotent (in-flight requests drain, the listening socket closes, the
    thread joins); the server is also a context manager that starts on
    ``__enter__`` and shuts down on ``__exit__``.
    """

    def __init__(self, telemetry=None, host: str = "127.0.0.1",
                 port: int = 9400, routes: Optional[list] = None):
        if routes is None:
            if telemetry is None:
                raise ValueError("MetricsServer needs telemetry or routes")
            routes = telemetry_routes(telemetry)
        route_table = list(routes)

        class Handler(BaseHTTPRequestHandler):
            def _dispatch(self, method: str, body: bytes):
                for row in route_table:
                    if len(row) == 3:
                        m, (prefix, ctype, fn) = "GET", row
                        call = fn
                    else:
                        m, prefix, ctype, fn = row
                        call = lambda fn=fn: fn(self.path, body)  # noqa: E731
                    if m != method or not self.path.startswith(prefix):
                        continue
                    result = call()
                    if result is None:
                        self.send_error(404)
                        return
                    status = 200
                    if isinstance(result, tuple):
                        status, result = result
                    payload = (
                        result.encode() if isinstance(result, str) else result
                    )
                    self.send_response(int(status))
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                    return
                self.send_error(404)

            def do_GET(self):  # noqa: N802 (stdlib API name)
                self._dispatch("GET", b"")

            def do_POST(self):  # noqa: N802 (stdlib API name)
                n = int(self.headers.get("Content-Length") or 0)
                self._dispatch("POST", self.rfile.read(n) if n else b"")

            def log_message(self, *args):  # quiet: scrapes are not events
                pass

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._thread: Optional[threading.Thread] = None
        self._closed = False

    @property
    def port(self) -> int:
        """The ACTUALLY-BOUND port (resolves ``port=0`` ephemeral binds)."""
        return self._server.server_address[1]

    @property
    def url(self) -> str:
        host = self._server.server_address[0]
        return f"http://{host}:{self.port}"

    def start(self) -> "MetricsServer":
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"nxdi-http-{self.port}",
        )
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._server.serve_forever()

    def shutdown(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self) -> "MetricsServer":
        if self._thread is None and not self._closed:
            self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
