"""Serving telemetry: always-on metrics + per-request lifecycle spans.

Nearly everything that determines NxDI's production latency is decided on
the HOST — bucket choice, padding waste, KV-block occupancy, speculation
acceptance, retrace events — so it is cheap to record continuously. This
package is the always-on layer the old pull-based tools
(``SubmodelProfiler``, ``bench.py`` hooks) now read from, so there is
exactly one timing path:

- :mod:`~nxdi_tpu.telemetry.registry` — counters/gauges/histograms with
  fixed log-spaced bounds (bounded memory, thread-safe).
- :mod:`~nxdi_tpu.telemetry.spans` — request spans (queue/pad/prefill/decode,
  TTFT, TPOT) in a bounded ring buffer.
- :mod:`~nxdi_tpu.telemetry.export` — Perfetto ``trace_events`` JSON and a
  stdlib ``/metrics`` HTTP endpoint; Prometheus text + JSON snapshot come
  from the registry.

Every application owns a :class:`Telemetry` (``app.telemetry``) built from
``TpuConfig(telemetry=...)``; the dispatch spine (``runtime/model_wrapper``),
generation adapter, block manager, speculation loops, and retrace guard all
record into it. CLI: ``python -m nxdi_tpu.cli.metrics``.

Metric catalog (labels in parens):

====================================  =========  ==================================
``nxdi_dispatches_total``             counter    (submodel, bucket, steps)
``nxdi_dispatch_seconds``             histogram  (submodel, bucket, steps) pad + enqueue
``nxdi_padding_waste_ratio``          histogram  (submodel)
``nxdi_real_tokens_total``            counter    (submodel)
``nxdi_padded_tokens_total``          counter    (submodel)
``nxdi_mixed_packed_tokens``          gauge      (bucket)
``nxdi_mixed_padding_waste``          gauge      (bucket)
``nxdi_requests_total``               counter
``nxdi_request_seconds``              histogram
``nxdi_request_ttft_seconds``         histogram
``nxdi_request_tpot_seconds``         histogram
``nxdi_request_tokens_in_total``      counter
``nxdi_request_tokens_out_total``     counter
``nxdi_kv_blocks_free``               gauge      (free + cache-reclaimable)
``nxdi_kv_blocks_used``               gauge      (non-reclaimable usage)
``nxdi_kv_block_forks_total``         counter    (PER BLOCK forked)
``nxdi_kv_block_frees_total``         counter    (PER BLOCK freed)
``nxdi_prefix_hits``                  counter
``nxdi_prefix_misses``                counter
``nxdi_prefix_evictions``             counter
``nxdi_prefix_cow_copies``            counter
``nxdi_prefix_cached_blocks``         gauge
``nxdi_prefix_tokens_saved_total``    counter
``nxdi_spec_accepted_tokens``         histogram  (path)
``nxdi_serve_queue_depth``            gauge
``nxdi_serve_slots_busy``             gauge
``nxdi_serve_preemptions_total``      counter
``nxdi_program_lowerings_total``      counter    (phase: warmup|serving)
``nxdi_moe_expert_form_programs_total`` counter  (submodel, form: dense|sorted)
``nxdi_program_mfu_pct``              gauge      (submodel, bucket, steps)
``nxdi_program_hbm_bw_pct``           gauge      (submodel, bucket, steps)
``nxdi_roofline_gap_ratio``           gauge      (submodel, bucket, steps)
``nxdi_spans_dropped_total``          counter
``nxdi_engine_steps_total``           counter
``nxdi_engine_step_seconds``          histogram
``nxdi_engine_host_seconds``          histogram  step wall - its ``fetch`` phase
``nxdi_engine_phase_seconds``         histogram  (phase) one engine step's phases
``nxdi_postmortems_total``            counter    (trigger)
``nxdi_slo_target_seconds``           gauge      (kind: ttft|tpot)
``nxdi_slo_requests_total``           counter    (outcome)
``nxdi_slo_breaches_total``           counter    (kind)
``nxdi_slo_attainment_pct``           gauge
``nxdi_slo_goodput_tok_s``            gauge
``nxdi_numerics_nonfinite_total``     counter    (submodel, bucket, kind: nan|inf)
``nxdi_numerics_max_abs_logit``       gauge      (submodel, bucket)
``nxdi_numerics_entropy``             histogram  (submodel, bucket)
``nxdi_numerics_margin``              histogram  (submodel, bucket)
``nxdi_sentinel_replays_total``       counter    (kind, outcome)
``nxdi_sentinel_replay_mismatch_total``  counter  (kind: shadow|preemption)
``nxdi_trace_hop_seconds``            histogram  (hop) distributed-trace hop duration
``nxdi_traces_dropped_total``         counter    hop spans evicted from the trace ring
====================================  =========  ==================================

The ``nxdi_numerics_*`` / ``nxdi_sentinel_*`` series belong to the numerics
sentinel (:mod:`~nxdi_tpu.telemetry.sentinel`, ``TpuConfig(sentinel=...)``)
and are pre-seeded at attach time so absence-of-errors is observable from
the first scrape; a nonzero NaN/Inf count or replay mismatch fires the
``numerics`` postmortem trigger through the flight recorder.

The ``nxdi_trace_*`` series belong to distributed request tracing
(:mod:`~nxdi_tpu.telemetry.tracing`, ``TelemetryConfig(trace=...)``): hop
spans land in a bounded per-replica :class:`~nxdi_tpu.telemetry.tracing.
TraceBuffer` served at ``/traces`` and federated by the fleet monitor
into per-request trace trees; the router tier owns a sibling pair of the
same two series in its own registry for the router-side hops.

Fleet observatory series (telemetry/fleet.py — emitted by a
:class:`~nxdi_tpu.telemetry.fleet.FleetMonitor`'s merged view, NOT by
replicas; every member gauge additionally gains a ``replica`` label there):

==========================================  =======  ========================
``nxdi_fleet_replicas``                     gauge    (state)
``nxdi_fleet_replica_state``                gauge    (replica) 0/1/2 code
``nxdi_fleet_health_transitions_total``     counter  (replica, from_state, to_state)
``nxdi_fleet_polls_total``                  counter  (replica, outcome)
``nxdi_fleet_snapshot_age_s``               gauge    (replica)
``nxdi_fleet_load_signal``                  gauge    (replica) router score
``nxdi_fleet_straggler_gap``                gauge    max-min load score
``nxdi_fleet_slo_attainment_pct``           gauge    from summed counters
==========================================  =======  ========================

Replica router series (nxdi_tpu/router — owned by a ``Router``'s registry
and federated into every fleet export via ``FleetMonitor.attach_registry``,
pre-seeded zero per target):

==========================================  =======  ========================
``nxdi_router_dispatches_total``            counter  (replica) placements
``nxdi_router_failovers_total``             counter  (replica = who FAILED it)
``nxdi_router_sheds_total``                 counter  backpressure rejections
``nxdi_router_drains_total``                counter  (replica) drains initiated
``nxdi_router_inflight``                    gauge    (replica) assigned now
==========================================  =======  ========================

The three roofline gauges are published by the cost observatory
(:func:`nxdi_tpu.analysis.costs.attach_cost_gauges`, wired at ``app.load()``):
at every export the measured mean dispatch latency is divided through each
program's :class:`~nxdi_tpu.analysis.costs.CostSheet`, and the sheet table
itself rides the JSON snapshot as ``_cost_sheets``. They exist only at
``detail="full"``, where that latency includes the device: at ``"basic"``
``nxdi_dispatch_seconds`` times the enqueue, a share of a peak computed from
it would be one no chip can give, and the three series are absent.

Engine-step phases (``Telemetry.phase``): while an engine step is open, the
step's host work is split into ``schedule``, ``kv``, ``pack``, ``pad``,
``enqueue``, ``fetch`` and ``emit`` (:data:`PHASES`). Each phase is a
``jax.profiler.TraceAnnotation("nxdi.step.<phase>")`` — so it sits on the
profiler's clock beside the device's operations, inside the step's
``StepTraceAnnotation("nxdi.step", step_num=StepRecord.step)`` — and its
``Telemetry.clock`` time adds into the open ``StepRecord.phases``, which
``nxdi_engine_phase_seconds{phase}`` observes as the step closes.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Callable, Dict, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from nxdi_tpu.telemetry import export as _export
from nxdi_tpu.telemetry.registry import (
    LENGTH_BOUNDS,
    RATIO_BOUNDS,
    TIME_BOUNDS_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_spaced_bounds,
    percentile_exact,
    percentile_from_buckets,
    prometheus_text,
)
from nxdi_tpu.telemetry.federation import (
    merge_perfetto_traces,
    merge_snapshots,
)
from nxdi_tpu.telemetry.fleet import (
    DEGRADED,
    HEALTHY,
    UNREACHABLE,
    FleetMonitor,
    LoadSignal,
    rank_load_signals,
)
from nxdi_tpu.telemetry.flight import FlightRecorder, StepRecord
from nxdi_tpu.telemetry.sentinel import NumericsSentinel
from nxdi_tpu.telemetry.slo import SloTracker, breach_kinds
from nxdi_tpu.telemetry.spans import NULL_SPAN, RequestSpan, SpanTracker
from nxdi_tpu.telemetry.tracing import (
    TraceBuffer,
    TraceContext,
    TraceSampler,
    assemble_traces,
    critical_path,
)

__all__ = [
    "Telemetry",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "SpanTracker",
    "RequestSpan",
    "NULL_SPAN",
    "FlightRecorder",
    "StepRecord",
    "NumericsSentinel",
    "SloTracker",
    "breach_kinds",
    "FleetMonitor",
    "LoadSignal",
    "rank_load_signals",
    "TraceBuffer",
    "TraceContext",
    "TraceSampler",
    "assemble_traces",
    "critical_path",
    "merge_snapshots",
    "merge_perfetto_traces",
    "HEALTHY",
    "DEGRADED",
    "UNREACHABLE",
    "MetricsServer",
    "prometheus_text",
    "percentile_from_buckets",
    "percentile_exact",
    "log_spaced_bounds",
    "TIME_BOUNDS_S",
    "RATIO_BOUNDS",
    "LENGTH_BOUNDS",
]

MetricsServer = _export.MetricsServer

DETAIL_LEVELS = ("off", "basic", "full")

#: the phases of one engine step, exhaustive and non-overlapping: who opens
#: each is in serving/engine.py (schedule, kv, pack, fetch, emit) and
#: runtime/model_wrapper.py (pad, enqueue)
PHASES = ("schedule", "kv", "pack", "pad", "enqueue", "fetch", "emit")
STEP_SPAN = "nxdi.step"
_PHASE_SPANS = {name: f"{STEP_SPAN}.{name}" for name in PHASES}
_NULL_CONTEXT = contextlib.nullcontext()


class _Phase:
    """One open phase (``Telemetry.phase``): a host span in the profiler's
    trace and, while an engine step is open, its time added to that
    StepRecord's ``phases``. A phase opened inside another (a sentinel replay
    dispatching from inside ``emit``) stops the outer one's count while it
    runs, so a step's phases never overlap. Outside an open step (the HF
    adapter calling a wrapper) a phase only annotates."""

    __slots__ = ("_tel", "_name", "_span", "_rec", "_outer", "_t0")

    def __init__(self, tel: "Telemetry", name: str):
        self._tel = tel
        self._name = name
        self._span = TraceAnnotation(_PHASE_SPANS[name])

    def _credit(self, now: float) -> None:
        phases = self._rec.phases
        phases[self._name] = phases.get(self._name, 0.0) + (now - self._t0)

    def __enter__(self):
        self._span.__enter__()
        fl = self._tel.flight
        rec = self._rec = fl.current if fl is not None else None
        if rec is not None:
            now = self._tel.clock()
            outer = self._outer = rec.open_phase
            if outer is not None:
                outer._credit(now)
            rec.open_phase = self
            self._t0 = now
        return self

    def __exit__(self, *exc):
        rec = self._rec
        if rec is not None:
            now = self._tel.clock()
            self._credit(now)
            outer = rec.open_phase = self._outer
            if outer is not None:
                outer._t0 = now
        self._span.__exit__(*exc)
        return False


class Telemetry:
    """The per-application telemetry facade: one registry + one span tracker
    + pre-bound metric families for the hot paths.

    Detail levels (``TpuConfig(telemetry=...)``):

    - ``"off"``   — nothing records; hot paths see one boolean check.
    - ``"basic"`` (default) — all metrics and spans record; dispatch latency
      is the HOST cost of a dispatch (pad + enqueue — JAX dispatch is async,
      so this does not include device execution and never forces a sync).
    - ``"full"``  — additionally ``sync_dispatch``: the host-path dispatch
      blocks until outputs are ready before recording, so the latency
      histogram measures true step latency (what ``SubmodelProfiler``
      turns on while attached). Device-resident chains are never synced.
    """

    def __init__(self, enabled: bool = True, detail: str = "basic",
                 max_spans: int = 256, clock=None, replica_id=None,
                 wall_clock=None, trace: bool = True,
                 trace_buffer: int = 256, trace_sample_rate: float = 1.0):
        if detail not in DETAIL_LEVELS:
            raise ValueError(
                f"telemetry detail must be one of {DETAIL_LEVELS}, got {detail!r}"
            )
        self.detail = detail
        self.enabled = bool(enabled) and detail != "off"
        self.sync_dispatch = detail == "full"
        self.clock = clock or time.perf_counter
        # wall-clock (unix seconds) for the _process snapshot stamp — kept
        # SEPARATE from `clock` (perf_counter domain) and injectable so the
        # fleet staleness tests can freeze it
        self.wall_clock = wall_clock or time.time
        # stable replica identity: the label every federated series carries
        # for this process (telemetry/fleet.py). Derived once; a fleet of
        # local replicas stays distinguishable because the pid differs.
        if replica_id is None:
            import os
            import socket

            replica_id = f"{socket.gethostname()}:{os.getpid()}"
        self.replica_id = str(replica_id)
        # serving role ("unified" | "prefill" | "decode") — stamped into the
        # _process snapshot extra so the fleet tier can role-split its
        # dispatch scoring; from_config copies TpuConfig.role here
        self.role = "unified"
        self._t0 = self.clock()
        self.registry = MetricsRegistry()
        # engine flight recorder (telemetry/flight.py), attached by the
        # serving engine via attach_flight(); rides record_dispatch, the
        # Perfetto export, and the JSON snapshot once attached
        self.flight = None
        # numerics sentinel (telemetry/sentinel.py), attached at app.load()
        # when TpuConfig(sentinel=...) is declared; the dispatch spine
        # (ModelWrapper.forward) feeds it each program's compiled-in
        # logit-health readout
        self.sentinel = None

        r = self.registry
        self.spans_dropped_total = r.counter(
            "nxdi_spans_dropped_total",
            "request spans evicted from the bounded ring buffer "
            "(nonzero = exported span history is truncated)",
        )
        # pre-seed the zero series: a scrape must SEE the counter before the
        # first eviction, so "no drops" and "not recording" read differently
        if self.enabled:
            self.spans_dropped_total.inc(0)
        self.spans = SpanTracker(self, max_spans=max_spans)
        # distributed tracing (telemetry/tracing.py): per-replica hop-span
        # ring + deterministic sampler for contexts THIS process mints.
        # Rides the enabled gate like every other surface — detail="off"
        # keeps its nothing-recorded contract and record_hop is a no-op.
        self.tracing = bool(trace) and self.enabled
        self.traces_dropped_total = r.counter(
            "nxdi_traces_dropped_total",
            "trace hop spans evicted from the bounded trace buffer "
            "(nonzero = exported trace history is truncated)",
        )
        self.trace_hop_seconds = r.histogram(
            "nxdi_trace_hop_seconds",
            "distributed-trace hop duration by typed hop name",
            ("hop",), bounds=TIME_BOUNDS_S,
        )
        self.trace_sampler = TraceSampler(trace_sample_rate)
        self.trace_buffer = TraceBuffer(
            trace_buffer, dropped_counter=self.traces_dropped_total,
            hop_seconds=self.trace_hop_seconds,
        )
        if self.tracing:
            # pre-seed the zero series: "no drops" and "not tracing" must
            # read differently from the first scrape
            self.traces_dropped_total.inc(0)
        disp_labels = ("submodel", "bucket", "steps")
        self.dispatches_total = r.counter(
            "nxdi_dispatches_total",
            "host dispatches per compiled (submodel, bucket[, steps]) program",
            disp_labels,
        )
        self.dispatch_seconds = r.histogram(
            "nxdi_dispatch_seconds",
            "host wall-clock of one dispatch in the wrapper, pad + enqueue: "
            "at detail=basic the device's time is NOT in it (sync_dispatch, "
            "detail=full, adds the wait for the device)",
            disp_labels, bounds=TIME_BOUNDS_S,
        )
        self.padding_waste = r.histogram(
            "nxdi_padding_waste_ratio",
            "(padded - real) / padded tokens per host-path dispatch",
            ("submodel",), bounds=RATIO_BOUNDS,
        )
        self.real_tokens_total = r.counter(
            "nxdi_real_tokens_total", "real tokens entering dispatch", ("submodel",)
        )
        self.padded_tokens_total = r.counter(
            "nxdi_padded_tokens_total",
            "tokens actually computed after bucket/batch padding", ("submodel",),
        )
        # mixed one-dispatch serving (runtime/model_wrapper.MixedModelWrapper):
        # last-seen packing per token-bucket rung — how full the packed
        # stream ran and what fraction of the rung was padding. Gauges (not
        # histograms) because the ladder is small and the flight recorder
        # already keeps the per-step series; pre-seeded zero per rung at app
        # registration (seed_mixed_buckets) so an idle rung is observable.
        self.mixed_packed_tokens = r.gauge(
            "nxdi_mixed_packed_tokens",
            "real packed tokens in the last mixed dispatch per bucket rung",
            ("bucket",),
        )
        self.mixed_padding_waste = r.gauge(
            "nxdi_mixed_padding_waste",
            "(bucket - packed) / bucket of the last mixed dispatch per rung",
            ("bucket",),
        )
        self.requests_total = r.counter(
            "nxdi_requests_total", "finished generation requests"
        )
        self.request_seconds = r.histogram(
            "nxdi_request_seconds", "end-to-end request wall-clock"
        )
        self.ttft_seconds = r.histogram(
            "nxdi_request_ttft_seconds", "time to first token"
        )
        self.tpot_seconds = r.histogram(
            "nxdi_request_tpot_seconds", "inter-token time (per generated token)"
        )
        self.tokens_in_total = r.counter(
            "nxdi_request_tokens_in_total", "prompt tokens received"
        )
        self.tokens_out_total = r.counter(
            "nxdi_request_tokens_out_total", "tokens generated"
        )
        self.kv_blocks_free = r.gauge(
            "nxdi_kv_blocks_free",
            "allocatable blocks in the paged-KV pool (free list + blocks "
            "the prefix cache can evict on demand)",
        )
        self.kv_blocks_used = r.gauge(
            "nxdi_kv_blocks_used",
            "non-reclaimable blocks in the paged-KV pool (live sequences; "
            "a warm prefix cache does NOT count as usage)",
        )
        self.kv_block_forks_total = r.counter(
            "nxdi_kv_block_forks_total",
            "blocks started shared via fork_prefix (counted per block)",
        )
        self.kv_block_frees_total = r.counter(
            "nxdi_kv_block_frees_total",
            "blocks released by sequence frees (counted per block)",
        )
        self.spec_accepted = r.histogram(
            "nxdi_spec_accepted_tokens",
            "tokens retired per speculation window (accepted + bonus)",
            ("path",), bounds=LENGTH_BOUNDS,
        )
        # serving-engine occupancy (nxdi_tpu/serving): the scheduler
        # publishes queue depth / busy slots every transition and counts
        # recompute-style preemptions
        self.serve_queue_depth = r.gauge(
            "nxdi_serve_queue_depth",
            "requests waiting for an engine slot (FCFS queue)",
        )
        self.serve_slots_busy = r.gauge(
            "nxdi_serve_slots_busy",
            "engine slots holding a running request",
        )
        self.serve_preemptions_total = r.counter(
            "nxdi_serve_preemptions_total",
            "requests evicted back to WAITING on KV-pool exhaustion "
            "(recompute-style preemption)",
        )
        self.lowerings_total = r.counter(
            "nxdi_program_lowerings_total",
            "program lowerings by phase (serving = post-seal retrace!)",
            ("phase",),
        )
        self.expert_form_programs = r.counter(
            "nxdi_moe_expert_form_programs_total",
            "lowered programs with an expert layer, by the form it chose from "
            "its shapes (ops/moe.py expert_form)",
            ("submodel", "form"),
        )
        # roofline gauges, set by the cost-observatory attachment
        # (analysis/costs.attach_cost_gauges) from measured-mean / CostSheet
        self.program_mfu_pct = r.gauge(
            "nxdi_program_mfu_pct",
            "achieved vs declared-chip-peak FLOP utilization per program",
            disp_labels,
        )
        self.program_hbm_bw_pct = r.gauge(
            "nxdi_program_hbm_bw_pct",
            "achieved vs declared-chip-peak HBM bandwidth per program",
            disp_labels,
        )
        self.roofline_gap_ratio = r.gauge(
            "nxdi_roofline_gap_ratio",
            "measured mean dispatch latency / CostSheet roofline floor",
            disp_labels,
        )
        self.phase_seconds = r.histogram(
            "nxdi_engine_phase_seconds",
            "time one engine step spent in each of its phases (observed as "
            "the step closes; a phase entered twice is summed first)",
            ("phase",), bounds=TIME_BOUNDS_S,
        )
        if self.enabled:
            for name in PHASES:
                self.phase_seconds.observe(0.0, n=0, phase=name)
        # export-time hooks: attachments run before every snapshot/scrape
        # (the cost observatory refreshes its gauges here); snapshot extras
        # merge additional keys (e.g. _cost_sheets) into the JSON snapshot.
        # Both are wrapped so a failing provider can never break an export.
        self._attachments: list = []
        self._snapshot_extras: Dict[str, Callable[[], object]] = {}
        # every JSON snapshot self-describes its origin: the federator ages
        # out replicas on snapshot_unix_s (NOT on transport success — a
        # wedged process keeps answering) and labels series by replica_id.
        # Gated on enabled: "off" keeps its nothing-recorded contract.
        if self.enabled:
            self.add_snapshot_extra("_process", self.process_info)
        if self.tracing:
            # hop spans ride every JSON snapshot so the fleet monitor's
            # regular /snapshot poll federates traces with no extra probe
            self.add_snapshot_extra("_traces", self.trace_buffer.snapshot)

    def process_info(self) -> dict:
        """Identity + freshness stamp embedded as the ``_process`` snapshot
        extra: who produced this snapshot, when (wall clock), and how long
        the process has been up (telemetry clock domain)."""
        import os

        return {
            "replica_id": self.replica_id,
            "role": self.role,
            "snapshot_unix_s": self.wall_clock(),
            "uptime_s": self.clock() - self._t0,
            "pid": os.getpid(),
        }

    # -- construction from config ------------------------------------------
    @classmethod
    def from_config(cls, tpu_config) -> "Telemetry":
        tc = getattr(tpu_config, "telemetry", None)
        if tc is None:
            tel = cls()
        else:
            tel = cls(
                enabled=getattr(tc, "enabled", True),
                detail=getattr(tc, "detail", "basic"),
                max_spans=getattr(tc, "max_spans", 256),
                replica_id=getattr(tc, "replica_id", None),
                trace=getattr(tc, "trace", True),
                trace_buffer=getattr(tc, "trace_buffer", 256),
                trace_sample_rate=getattr(tc, "trace_sample_rate", 1.0),
            )
        tel.role = getattr(tpu_config, "role", "unified")
        return tel

    # -- distributed tracing -------------------------------------------------
    def mint_trace(self):
        """A fresh root :class:`~nxdi_tpu.telemetry.tracing.TraceContext`
        for a request that arrived without a (valid) ``traceparent`` —
        sampled by the deterministic credit accumulator. None when tracing
        is off, so callers keep one None-check like every other surface."""
        if not self.tracing:
            return None
        return TraceContext.mint(sampled=self.trace_sampler.sample())

    def record_hop(self, hop: str, trace, *, t_start: float,
                   duration_s: float, parent_span_id=None, attrs=None):
        """Record one finished hop span against ``trace`` (a TraceContext).
        No-op — returning None — when tracing is off or the trace is
        unsampled, so hot paths pay one boolean check. Returns the hop's
        span id otherwise (the parent for the request's next hop).
        ``t_start`` is WALL-clock unix seconds: hop spans join across
        processes and cannot ride the per-process telemetry clock."""
        if not self.tracing or trace is None or not trace.sampled:
            return None
        return self.trace_buffer.record(
            hop, trace.trace_id,
            parent_span_id if parent_span_id is not None else trace.span_id,
            t_start=t_start, duration_s=duration_s,
            replica=self.replica_id, attrs=attrs,
        )

    def trace_spans(self):
        """Retained hop spans (the ``/traces`` endpoint body)."""
        if not self.tracing:
            return []
        return self.trace_buffer.snapshot()

    # -- hot-path recorders -------------------------------------------------
    def phase(self, name: str):
        """Context manager around one phase of the engine step (``name`` in
        :data:`PHASES`); the shared null context when telemetry is off."""
        if not self.enabled:
            return _NULL_CONTEXT
        return _Phase(self, name)

    def step_span(self, step_num: int):
        """``StepTraceAnnotation("nxdi.step", step_num=...)`` around one
        engine step, so a flight record and the profiler's spans join by
        step number; the shared null context when telemetry is off."""
        if not self.enabled:
            return _NULL_CONTEXT
        return StepTraceAnnotation(STEP_SPAN, step_num=step_num)

    def record_dispatch(
        self,
        submodel: str,
        bucket,
        steps,
        seconds: float,
        real_tokens: Optional[int] = None,
        padded_tokens: Optional[int] = None,
    ) -> None:
        labels = dict(submodel=submodel, bucket=str(bucket), steps=str(steps))
        self.dispatches_total.inc(**labels)
        self.dispatch_seconds.observe(seconds, **labels)
        fl = self.flight
        if fl is not None:
            # the open StepRecord's program attribution — same numbers as
            # the registry, one None-check on the non-serving hot path
            fl._note_dispatch(submodel, bucket, steps, seconds)
        if real_tokens is not None and padded_tokens:
            self.real_tokens_total.inc(real_tokens, submodel=submodel)
            self.padded_tokens_total.inc(padded_tokens, submodel=submodel)
            self.padding_waste.observe(
                (padded_tokens - real_tokens) / padded_tokens, submodel=submodel
            )

    def seed_mixed_buckets(self, buckets) -> None:
        """Pre-seed the mixed packing gauges with a zero per token-bucket
        rung (application registration time): a scrape distinguishes "rung
        never dispatched" from "metric not recorded"."""
        if not self.enabled:
            return
        for b in buckets:
            self.mixed_packed_tokens.set(0.0, bucket=str(b))
            self.mixed_padding_waste.set(0.0, bucket=str(b))

    def record_mixed(self, bucket, packed_tokens: int, padded_tokens: int) -> None:
        """One mixed dispatch's packing efficiency (MixedModelWrapper)."""
        labels = dict(bucket=str(bucket))
        self.mixed_packed_tokens.set(float(packed_tokens), **labels)
        if padded_tokens:
            self.mixed_padding_waste.set(
                (padded_tokens - packed_tokens) / padded_tokens, **labels
            )

    def start_request(self, tokens_in: int = 0, t_start=None,
                      session_id=None, trace=None):
        """``t_start`` (optional, ``clock`` domain) backdates the span to the
        request's true arrival so TTFT includes queueing before this call;
        ``session_id`` tags the span with its conversation identity (the
        router tier's affinity key); ``trace`` (optional TraceContext)
        stamps the span with its distributed-trace identity so postmortem
        bundles link back to the fleet trace."""
        if not self.enabled:
            return NULL_SPAN
        return self.spans.start(
            tokens_in=tokens_in, t_start=t_start, session_id=session_id,
            trace=trace,
        )

    def record_spec_window(self, counts, path: str) -> None:
        """Accepted-length histogram per speculation window; ``counts`` is a
        per-row iterable of tokens retired (accepted + bonus)."""
        for c in counts:
            self.spec_accepted.observe(float(c), path=path)

    def record_lowering(self, label: str, post_seal: bool) -> None:
        self.lowerings_total.inc(phase="serving" if post_seal else "warmup")

    def record_expert_form(self, submodel: str, form: str) -> None:
        self.expert_form_programs.inc(submodel=submodel, form=form)

    def attach_flight(self, recorder) -> None:
        """Adopt an engine's :class:`~nxdi_tpu.telemetry.flight.FlightRecorder`:
        ``record_dispatch`` feeds its open StepRecord, the Perfetto export
        grows the per-slot engine timeline, and every JSON snapshot carries
        a ``_flight`` summary. The LAST attached recorder wins (one live
        engine per app is the supported shape)."""
        self.flight = recorder
        self.add_snapshot_extra("_flight", recorder.summary)
        if self.sentinel is not None:
            # an app-attached sentinel gains the engine's postmortem path
            self.sentinel.flight = recorder

    def attach_sentinel(self, sentinel) -> None:
        """Adopt a :class:`~nxdi_tpu.telemetry.sentinel.NumericsSentinel`:
        every host-path dispatch with compiled-in logit stats records
        through it, and its summary rides the JSON snapshot as
        ``_sentinel``. The LAST attached sentinel wins (one live app)."""
        self.sentinel = sentinel
        if self.flight is not None and sentinel.flight is None:
            sentinel.flight = self.flight
        self.add_snapshot_extra("_sentinel", sentinel.summary)

    # -- export-time hooks --------------------------------------------------
    def attach(self, fn: Callable[[], None]) -> None:
        """Register a hook run before every export (snapshot / Prometheus
        text) — how derived gauges stay current without a hot-path cost."""
        self._attachments.append(fn)

    def add_snapshot_extra(self, key: str, fn: Callable[[], object]) -> None:
        """Merge ``{key: fn()}`` into every JSON snapshot (and therefore
        into ``--metrics-out`` dumps and the ``/metrics.json`` endpoint)."""
        self._snapshot_extras[key] = fn

    def _run_attachments(self) -> None:
        for fn in list(self._attachments):
            try:
                fn()
            except Exception:
                logging.getLogger("nxdi_tpu").warning(
                    "telemetry attachment failed; export continues", exc_info=True
                )

    # -- export -------------------------------------------------------------
    def snapshot(self) -> dict:
        self._run_attachments()
        snap = self.registry.snapshot()
        snap["_spans"] = self.spans.to_list()
        for key, fn in list(self._snapshot_extras.items()):
            try:
                snap[key] = fn()
            except Exception:
                logging.getLogger("nxdi_tpu").warning(
                    "snapshot extra %r failed; export continues", key,
                    exc_info=True,
                )
        return snap

    def prometheus_text(self) -> str:
        self._run_attachments()
        return prometheus_text(self.registry)

    def perfetto_trace(self, process_name: str = "nxdi_tpu") -> dict:
        return _export.perfetto_trace(
            self.spans, process_name=process_name, flight=self.flight
        )

    def write_perfetto_trace(self, path: str, process_name: str = "nxdi_tpu") -> dict:
        return _export.write_perfetto_trace(
            self.spans, path, process_name=process_name, flight=self.flight
        )

    def serve(self, host: str = "127.0.0.1", port: int = 9400) -> "MetricsServer":
        """Start a daemon-thread HTTP server exposing ``/metrics`` (Prometheus
        text), ``/metrics.json``, and ``/trace.json``."""
        return MetricsServer(self, host=host, port=port).start()

    def reset(self) -> None:
        self.registry.reset()
        self.spans.reset()
