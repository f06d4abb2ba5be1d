"""Serving flight recorder: per-step engine timeline + postmortem capture.

ROADMAP items 3 (replica router) and 5 (SLO-aware scheduling) both need to
know what the engine *decided* each step — and "Kernel Looping" (PAPERS.md)
argues the host-side sync boundary between dispatches is where decode
latency hides. This module records both continuously: every
``InferenceEngine.step()`` emits one :class:`StepRecord` into a bounded
ring buffer, carrying

- the scheduling decisions: admissions (with resume flag), chunk prefills,
  the decode dispatch (rows occupied, multistep rung, padding rows),
  preemptions (with the vacated slot), retirements,
- the resource picture: free KV blocks, queue depth, busy slots,
- **where the step's wall-clock went**: ``phases`` holds the seconds the
  step spent in each of its phases (``telemetry.PHASES``: schedule, kv,
  pack, pad, enqueue, fetch, emit), added up by ``Telemetry.phase`` where
  the work happens, on ``Telemetry.clock``; ``other_s = wall - sum(phases)``
  is the time under no phase, so nothing is lost silently. ``fetch`` is the
  one phase in which the host waits for the device's tokens, so
  ``host_s = wall - phases["fetch"]`` is the host's own work in the step.
  On a step in the synchronous order the device has nothing to do
  meanwhile; on a ``chained`` step (its decode dispatched before the
  previous step's tokens were collected: serving/engine.py) that work runs
  beside the previous program. ``dispatch_s`` is the wrapper's own
  ``pad`` + ``enqueue`` time per program dispatch (the
  ``nxdi_dispatch_seconds`` path feeds it via ``Telemetry.record_dispatch``,
  ONE timing source): at ``detail="basic"`` the time to hand the device its
  work, never the device's time; at ``"full"`` dispatches block on device
  completion and it includes it.

Trigger-based **postmortem capture**: on SLO breach (fed by
:class:`~nxdi_tpu.telemetry.slo.SloTracker`), preemption storm
(>= ``storm_preemptions`` recompute preemptions inside the last
``storm_window`` steps), or a retrace-guard trip, the recorder dumps a JSON
bundle — trigger, breaching request's span, every StepRecord overlapping
its lifetime, scheduler queue state, and a full metrics snapshot — to
``TelemetryConfig(postmortem_dir=...)``; a manual dump is reachable from
``python -m nxdi_tpu.cli.flightrec`` and the ``/postmortem`` endpoint of
``cli.metrics --serve`` / ``cli.serve --serve``.

The ring rides the Perfetto export: one track per decode slot
(prefill / decode / preempted segments) plus a host-overhead track (each
step's ``host_s``, its phases in the arguments), so a ``cli.serve`` run
opens in the Perfetto UI as a per-slot Gantt chart. The same step, by number,
is the ``nxdi.step`` span of a ``jax.profiler`` trace.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

logger = logging.getLogger("nxdi_tpu")

#: postmortem trigger names (the ``trigger`` field of every bundle).
#: ``numerics`` is fired by the sentinel (telemetry/sentinel.py): a NaN/Inf
#: logit burst, a shadow-replay divergence, or a preemption-replay mismatch
#: (``detail["kind"]`` names which).
TRIGGERS = (
    "slo_breach", "preemption_storm", "retrace_guard", "numerics", "manual",
    "fault_recovery",
)


class StepRecord:
    """One ``InferenceEngine.step()``: what the engine decided and where the
    wall-clock went. A handful of small lists — never per-token."""

    __slots__ = (
        "step", "t_start", "t_end", "admitted", "prefills", "decode",
        "mixed", "preempted", "retired", "programs", "kv_blocks_free",
        "queue_depth", "slots_busy", "dispatch_s", "host_s", "faults",
        "phases", "open_phase", "moe_held_pairs", "moe_routed_layers",
        "prefill_moe_held_pairs", "prefill_moe_expert_rows",
        "kv_live_tokens", "kv_window_rows_held", "kv_bytes_held",
        "chained", "overrun_tokens", "sparse_blocks_read", "sparse_blocks_live",
    )

    def __init__(self, step: int, t_start: float):
        self.step = step
        self.t_start = t_start
        self.t_end: Optional[float] = None
        #: [{request_id, slot, resumed}] — placements this step
        self.admitted: List[dict] = []
        #: [{request_id, slot, submodel, start, tokens}] — one per chunk
        self.prefills: List[dict] = []
        #: {submodel, steps, rows: [{slot, request_id}], batch, padding_rows}
        self.decode: Optional[dict] = None
        #: one-dispatch mixed step (mixed_dispatch): {submodel, bucket,
        #: prefill_rows, decode_rows, packed_tokens, padded_tokens} — the
        #: prefill/decode split is what cli.flightrec renders as packing
        #: efficiency
        self.mixed: Optional[dict] = None
        #: [{request_id, slot}] — slot is the row the victim vacated
        self.preempted: List[dict] = []
        #: [{kind, error, requeued, failed}] — step-fault recoveries: the
        #: classified fault and how many running requests it requeued vs
        #: error-finished (recovery budget exhausted)
        self.faults: List[dict] = []
        #: [{request_id, slot, reason}]
        self.retired: List[dict] = []
        #: {(submodel, bucket, steps) -> {dispatches, seconds}} — fed by
        #: Telemetry.record_dispatch while this step is open, so program
        #: keys and latencies are EXACTLY what the registry saw
        self.programs: Dict[tuple, Dict[str, float]] = {}
        self.kv_blocks_free: Optional[int] = None
        self.queue_depth = 0
        self.slots_busy = 0
        self.dispatch_s = 0.0
        #: wall - phases["fetch"], set as the step closes
        self.host_s = 0.0
        #: {phase -> seconds} — added to by Telemetry.phase while the step
        #: is open; a phase entered twice (a prefill and a decode) sums
        self.phases: Dict[str, float] = {}
        #: the innermost phase open right now (Telemetry.phase keeps it)
        self.open_phase = None
        #: (row, expert) pairs of this step's token generation that fell on
        #: experts this program holds, summed over its routed layers, as the
        #: step program counted them (batch-padding rows included); None for
        #: a model whose program returns no such count
        self.moe_held_pairs: Optional[int] = None
        #: the routed layers those pairs were summed over
        self.moe_routed_layers: Optional[int] = None
        #: the same pairs of this step's PREFILLS whose expert layers took the
        #: compact form (the real rows': bucket padding has no slot), and the
        #: slot rows their expert matmuls multiplied for them (held experts x
        #: slots an expert x trips, summed over the routed layers); None where
        #: no such prefill ran in the step
        self.prefill_moe_held_pairs: Optional[int] = None
        self.prefill_moe_expert_rows: Optional[int] = None
        #: a model with block-sparse attention layers: the pool blocks this
        #: step's token generation READ and the blocks visible to it (what a
        #: dense read would have taken), summed over rows, KV heads and sparse
        #: layers as the step program counted them; None for every other model
        self.sparse_blocks_read: Optional[int] = None
        self.sparse_blocks_live: Optional[int] = None
        #: a cache tree with a store per SLOT beside the block pool (mimo-v2's
        #: window layers): the tokens of the seated requests at the end of the
        #: step, the ring rows their slots hold (rows a slot x busy slots),
        #: and the bytes of cache held for them (used blocks + those rows, as
        #: the arrays store them); None for every other tree
        self.kv_live_tokens: Optional[int] = None
        self.kv_window_rows_held: Optional[int] = None
        self.kv_bytes_held: Optional[int] = None
        #: this step's decode was dispatched before the previous step's
        #: tokens were collected (its input ids never left the device)
        self.chained = False
        #: tokens this step's decode made for rows that had left their slot
        #: by the collect (an unforeseen EOS), dropped unemitted
        self.overrun_tokens = 0

    @property
    def wall_s(self) -> float:
        return (self.t_end - self.t_start) if self.t_end is not None else 0.0

    @property
    def other_s(self) -> float:
        """Wall time under no phase."""
        return self.wall_s - sum(self.phases.values())

    def overlaps(self, t0: float, t1: float) -> bool:
        end = self.t_end if self.t_end is not None else self.t_start
        return end >= t0 and self.t_start <= t1

    def to_dict(self) -> dict:
        return {
            "step": self.step,
            "t_start": self.t_start,
            "t_end": self.t_end,
            "wall_s": self.wall_s,
            "dispatch_s": self.dispatch_s,
            "host_s": self.host_s,
            "phases": dict(self.phases),
            "other_s": self.other_s,
            "admitted": list(self.admitted),
            "prefills": list(self.prefills),
            "decode": self.decode,
            "mixed": self.mixed,
            "preempted": list(self.preempted),
            "retired": list(self.retired),
            "faults": list(self.faults),
            "programs": [
                {
                    "submodel": k[0], "bucket": k[1], "steps": k[2],
                    "dispatches": v["dispatches"], "seconds": v["seconds"],
                }
                for k, v in sorted(self.programs.items())
            ],
            "kv_blocks_free": self.kv_blocks_free,
            "queue_depth": self.queue_depth,
            "slots_busy": self.slots_busy,
            "moe_held_pairs": self.moe_held_pairs,
            "moe_routed_layers": self.moe_routed_layers,
            "prefill_moe_held_pairs": self.prefill_moe_held_pairs,
            "prefill_moe_expert_rows": self.prefill_moe_expert_rows,
            "kv_live_tokens": self.kv_live_tokens,
            "kv_window_rows_held": self.kv_window_rows_held,
            "kv_bytes_held": self.kv_bytes_held,
            "chained": self.chained,
            "overrun_tokens": self.overrun_tokens,
            "sparse_blocks_read": self.sparse_blocks_read,
            "sparse_blocks_live": self.sparse_blocks_live,
        }


class FlightRecorder:
    """Bounded StepRecord ring + postmortem triggers, owned by one engine.

    ``state_fn`` returns the scheduler's queue/slot state for bundles;
    ``retrace_guard`` (optional) is polled every step for new violations.
    Construction registers the engine-step metric families on the
    telemetry registry (idempotent).
    """

    def __init__(
        self,
        telemetry,
        num_slots: int,
        max_records: int = 512,
        postmortem_dir: Optional[str] = None,
        storm_window: int = 32,
        storm_preemptions: int = 8,
        state_fn: Optional[Callable[[], dict]] = None,
        retrace_guard=None,
    ):
        self.telemetry = telemetry
        self.num_slots = int(num_slots)
        self.max_records = int(max_records)
        self.postmortem_dir = postmortem_dir
        self.storm_window = int(storm_window)
        self.storm_preemptions = int(storm_preemptions)
        self.state_fn = state_fn
        self.retrace_guard = retrace_guard
        # one lock around the ring and the postmortem index: the engine
        # thread appends while the MetricsServer thread (/trace.json,
        # /postmortem, snapshot extras) iterates — an unguarded deque read
        # raises "mutated during iteration" on the probe surface. The open
        # record (``current``) stays engine-thread-only and lock-free.
        self._lock = threading.Lock()
        self.records: Deque[StepRecord] = deque()  # guarded_by: _lock
        self.records_dropped = 0  # guarded_by: _lock
        #: {trigger, step, path} — bounded index of captured bundles
        self.postmortems: List[dict] = []  # guarded_by: _lock
        self._bundle_seq = 0  # monotonic: filenames never collide
        self.current: Optional[StepRecord] = None  # lock-free: engine-thread-only open record
        # scheduling events raised BETWEEN steps (a forced preemption from a
        # driver's before_step hook, a direct scheduler call) buffer here
        # and fold into the NEXT step's record — they shape that step's
        # decisions, and nothing may vanish just for arriving early
        self._pending: List[tuple] = []  # lock-free: engine-thread-only between-step buffer
        # ``steps``/bundles read this cross-thread: a single int store is
        # atomic under the GIL, and a stale count only lags the liveness probe
        self._step_counter = 0  # lock-free: engine-thread-written monotonic int
        # rolling per-step preemption counts for the storm trigger: O(1)
        # per step instead of rescanning the ring
        self._recent_preempts: Deque[int] = deque()  # lock-free: engine-thread-only storm window
        self._recent_preempt_sum = 0  # lock-free: engine-thread-only
        self._storm_fired_step: Optional[int] = None  # lock-free: engine-thread-only cooldown mark
        self._seen_violations = (  # lock-free: engine-thread-only retrace cursor
            len(retrace_guard.violations) if retrace_guard is not None else 0
        )
        r = telemetry.registry
        self.steps_total = r.counter(
            "nxdi_engine_steps_total", "InferenceEngine.step() iterations"
        )
        self.step_seconds = r.histogram(
            "nxdi_engine_step_seconds", "wall-clock per engine step"
        )
        self.host_seconds = r.histogram(
            "nxdi_engine_host_seconds",
            "engine step wall-clock minus its fetch phase: the time the "
            "engine kept the device without work to wait for",
        )
        self.postmortems_total = r.counter(
            "nxdi_postmortems_total", "postmortem bundles by trigger", ("trigger",)
        )

    # -- the per-step protocol (driven by InferenceEngine.step) -------------
    def begin_step(self) -> StepRecord:
        rec = StepRecord(self._step_counter, self.telemetry.clock())
        self._step_counter += 1
        self.current = rec
        for field, entry in self._pending:
            getattr(rec, field).append(entry)
        self._pending.clear()
        return rec

    def _append(self, field: str, entry: dict) -> None:
        rec = self.current
        if rec is None:
            self._pending.append((field, entry))
        else:
            getattr(rec, field).append(entry)

    def _note_dispatch(
        self, submodel: str, bucket, steps, seconds: float
    ) -> None:
        """Called by ``Telemetry.record_dispatch`` while a step is open: the
        step's program attribution IS the registry's, never a re-derivation."""
        rec = self.current
        if rec is None:
            return
        key = (submodel, str(bucket), str(steps))
        entry = rec.programs.get(key)
        if entry is None:
            entry = rec.programs[key] = {"dispatches": 0, "seconds": 0.0}
        entry["dispatches"] += 1
        entry["seconds"] += seconds
        rec.dispatch_s += seconds

    def record_admission(
        self,
        request_id,
        slot: int,
        resumed: bool,
        cached_tokens: int = 0,
        total_tokens: int = 0,
    ) -> None:
        """One admission: ``cached_tokens`` of the request's ``total_tokens``
        (re)prefill arrived via a prefix-cache / fork hit — the timeline's
        ``cached=K/N`` column."""
        self._append(
            "admitted",
            {
                "request_id": request_id, "slot": slot, "resumed": resumed,
                "cached": cached_tokens, "total": total_tokens,
            },
        )

    def record_prefill(
        self, request_id, slot, submodel: str, start: int, tokens: int
    ) -> None:
        self._append("prefills", {
            "request_id": request_id, "slot": slot, "submodel": submodel,
            "start": start, "tokens": tokens,
        })

    def record_decode(
        self,
        submodel: str,
        steps: int,
        rows,
        batch: int,
        tokens_emitted: Optional[int] = None,
        chained: bool = False,
    ) -> None:
        if self.current is not None:
            self.current.chained = chained
            self.current.decode = {
                "submodel": submodel,
                "steps": steps,
                "rows": [
                    {"slot": slot, "request_id": r.request_id} for slot, r in rows
                ],
                "batch": batch,
                "padding_rows": batch - len(rows),
                # REAL tokens the host unpacked from the dispatch: multistep
                # and device-loop rows can finish mid-window, so intent-time
                # rows * steps overstates it. None until the engine notes it
                # (single/multistep note after unpack; the device loop passes
                # it directly — the launch already ran when it records).
                "tokens_emitted": tokens_emitted,
            }

    def note_decode_tokens(self, tokens: int, overrun: int = 0, rec=None) -> None:
        """Fill a step's decode record with the real emitted-token count once
        the host has unpacked the dispatch: the open step's, or ``rec``, the
        step that dispatched it (a chained decode is collected a step later,
        its record already in the ring)."""
        rec = rec or self.current
        if rec is not None and rec.decode is not None:
            rec.decode["tokens_emitted"] = int(tokens)
            rec.overrun_tokens = int(overrun)

    def note_moe_held_pairs(self, pairs: int, routed_layers: int, rec=None) -> None:
        rec = rec or self.current
        if rec is not None:
            rec.moe_held_pairs = (rec.moe_held_pairs or 0) + int(pairs)
            rec.moe_routed_layers = int(routed_layers)

    def note_sparse_blocks(self, read: int, live: int, rec=None) -> None:
        rec = rec or self.current
        if rec is not None:
            rec.sparse_blocks_read = (rec.sparse_blocks_read or 0) + int(read)
            rec.sparse_blocks_live = (rec.sparse_blocks_live or 0) + int(live)

    def note_prefill_moe(self, pairs: int, expert_rows: int) -> None:
        rec = self.current
        if rec is not None:
            rec.prefill_moe_held_pairs = (rec.prefill_moe_held_pairs or 0) + int(pairs)
            rec.prefill_moe_expert_rows = (rec.prefill_moe_expert_rows or 0) + int(expert_rows)

    def record_mixed(
        self,
        submodel: str,
        bucket: int,
        prefill_rows: int,
        decode_rows: int,
        packed_tokens: int,
        padded_tokens: int,
    ) -> None:
        """One unified mixed prefill+decode dispatch (mixed_dispatch): row
        split + packing so timelines show how full the packed stream ran."""
        if self.current is not None:
            self.current.mixed = {
                "submodel": submodel,
                "bucket": int(bucket),
                "prefill_rows": int(prefill_rows),
                "decode_rows": int(decode_rows),
                "packed_tokens": int(packed_tokens),
                "padded_tokens": int(padded_tokens),
            }

    def record_preemption(self, request_id, slot) -> None:
        self._append("preempted", {"request_id": request_id, "slot": slot})

    def record_fault(
        self, kind: str, error: str, requeued: int, failed: int
    ) -> None:
        """One recovered step fault: its taxonomy ``kind``, the error text,
        and how the RUNNING set was disposed (requeued vs error-finished)."""
        self._append(
            "faults",
            {"kind": kind, "error": error, "requeued": requeued, "failed": failed},
        )

    def record_retirement(self, request_id, slot, reason: str) -> None:
        self._append(
            "retired", {"request_id": request_id, "slot": slot, "reason": reason}
        )

    def end_step(
        self,
        queue_depth: int,
        slots_busy: int,
        kv_blocks_free: Optional[int],
        kv_held: Optional[tuple] = None,
    ) -> StepRecord:
        """Close the open record, fold it into the ring + metrics, and run
        the step-scoped triggers (storm, retrace). Returns the record."""
        rec = self.current
        assert rec is not None, "end_step without begin_step"
        self.current = None
        rec.t_end = self.telemetry.clock()
        rec.queue_depth = int(queue_depth)
        rec.slots_busy = int(slots_busy)
        rec.kv_blocks_free = kv_blocks_free
        if kv_held is not None:  # (live tokens, window rows held, bytes held)
            rec.kv_live_tokens, rec.kv_window_rows_held, rec.kv_bytes_held = kv_held
        rec.host_s = rec.wall_s - rec.phases.get("fetch", 0.0)
        with self._lock:
            self.records.append(rec)
            if len(self.records) > self.max_records:
                self.records.popleft()
                self.records_dropped += 1
        self.steps_total.inc()
        self.step_seconds.observe(rec.wall_s)
        self.host_seconds.observe(rec.host_s)
        for name, seconds in rec.phases.items():
            self.telemetry.phase_seconds.observe(seconds, phase=name)
        self._check_storm(rec)
        self._check_retrace(rec)
        return rec

    # -- triggers -----------------------------------------------------------
    def _check_storm(self, rec: StepRecord) -> None:
        if len(self._recent_preempts) == self.storm_window:
            self._recent_preempt_sum -= self._recent_preempts.popleft()
        self._recent_preempts.append(len(rec.preempted))
        self._recent_preempt_sum += len(rec.preempted)
        if self._storm_fired_step is not None and (
            rec.step <= self._storm_fired_step + self.storm_window
        ):
            return  # cooldown: one bundle per storm, not one per step
        n = self._recent_preempt_sum
        if n >= self.storm_preemptions:
            self._storm_fired_step = rec.step
            self.postmortem(
                "preemption_storm",
                detail={
                    "preemptions": n,
                    "window_steps": self.storm_window,
                    "threshold": self.storm_preemptions,
                },
            )

    def _check_retrace(self, rec: StepRecord) -> None:
        guard = self.retrace_guard
        if guard is None:
            return
        n = len(guard.violations)
        if n > self._seen_violations:
            new = list(guard.violations[self._seen_violations:])
            self._seen_violations = n
            self.postmortem("retrace_guard", detail={"violations": new})

    # -- queries (safe from any thread) -------------------------------------
    @property
    def steps(self) -> int:
        """Engine steps begun so far (the /healthz liveness number)."""
        return self._step_counter

    def snapshot_records(self) -> List[StepRecord]:
        """Consistent copy of the ring — what every cross-thread reader
        (Perfetto export, bundles, CLI tables) iterates."""
        with self._lock:
            return list(self.records)

    def records_overlapping(self, t0: float, t1: float) -> List[StepRecord]:
        """Every retained StepRecord overlapping ``[t0, t1]`` (a request's
        span window) in step order."""
        return [r for r in self.snapshot_records() if r.overlaps(t0, t1)]

    def summary(self) -> dict:
        """Small dict for the JSON-snapshot extra (``_flight``) — the full
        ring only travels in postmortem bundles and the Perfetto export."""
        with self._lock:
            last = self.records[-1] if self.records else None
            n, dropped = len(self.records), self.records_dropped
            postmortems = list(self.postmortems)
        return {
            "steps": self._step_counter,
            "records": n,
            "records_dropped": dropped,
            "num_slots": self.num_slots,
            "postmortems": postmortems,
            "last_step": last.to_dict() if last is not None else None,
        }

    # -- postmortem capture -------------------------------------------------
    def postmortem(
        self,
        trigger: str,
        detail: Optional[dict] = None,
        request_span=None,
        request_id=None,
    ) -> dict:
        """Capture a bundle: trigger + breaching request's span + every
        StepRecord overlapping its lifetime (the whole ring for span-less
        triggers) + scheduler queue state + a full metrics snapshot. Written
        to ``postmortem_dir`` when configured; always returned."""
        if trigger not in TRIGGERS:
            raise ValueError(f"trigger must be one of {TRIGGERS}, got {trigger!r}")
        tel = self.telemetry
        now = tel.clock()
        if request_span is not None:
            t0 = request_span.t_start
            t1 = request_span.t_end if request_span.t_end is not None else now
            records = self.records_overlapping(t0, t1)
            span_dict = request_span.to_dict()
        else:
            records = self.snapshot_records()
            span_dict = None
        # one lock block for everything the engine thread mutates: the ring
        # drop counter (end_step bumps it under the lock) and the bundle
        # sequence number — a torn pair here would misname or misreport a
        # bundle captured mid-step
        with self._lock:
            dropped_ring = self.records_dropped
            seq = self._bundle_seq
            self._bundle_seq += 1
        dropped = tel.spans_dropped_total.total() + dropped_ring
        # distributed-trace correlation: when the breaching request carries
        # a trace, the bundle names it and embeds this replica's retained
        # hop spans for it — a postmortem reader can jump straight from the
        # bundle to the fleet-wide waterfall (cli.trace --trace-id)
        trace_id = (span_dict or {}).get("trace_id")
        trace_hops = (
            tel.trace_buffer.spans_for(trace_id)
            if trace_id and getattr(tel, "tracing", False) else []
        )
        bundle = {
            "trigger": trigger,
            "detail": detail or {},
            "t": now,
            "step": self._step_counter - 1,
            "request_id": request_id,
            "trace_id": trace_id,
            "trace_hops": trace_hops,
            "request_span": span_dict,
            "step_records": [r.to_dict() for r in records],
            "scheduler": self.state_fn() if self.state_fn is not None else None,
            "metrics": tel.snapshot(),
            # nonzero = the ring/span buffers evicted history this bundle
            # can no longer show — read the timeline as truncated
            "history_dropped": dropped,
            "path": None,
        }
        self.postmortems_total.inc(trigger=trigger)
        if self.postmortem_dir is not None:
            try:
                os.makedirs(self.postmortem_dir, exist_ok=True)
                name = (
                    f"postmortem_{trigger}_step{bundle['step']}_{seq}.json"
                )
                path = os.path.join(self.postmortem_dir, name)
                with open(path, "w") as f:
                    json.dump(bundle, f, indent=2)
                bundle["path"] = path
            except OSError:
                logger.warning(
                    "flight recorder could not write the postmortem bundle; "
                    "serving continues", exc_info=True,
                )
        with self._lock:
            self.postmortems.append(
                {"trigger": trigger, "step": bundle["step"],
                 "path": bundle["path"]}
            )
            del self.postmortems[:-32]  # bound the index, keep the newest
        logger.warning(
            "flight recorder postmortem: trigger=%s step=%d%s",
            trigger, bundle["step"],
            f" -> {bundle['path']}" if bundle["path"] else " (in-memory)",
        )
        return bundle
