"""Continuous-batching inference engine over the compiled program ladder.

``InferenceEngine.step()`` is one scheduler iteration over the app's
fixed-shape AOT programs — the host-side loop that turns them into a
streaming multi-tenant server (the role vLLM plays for the reference
stack):

1. **Prefill** admitted requests into free slots: one CTE dispatch per
   request (``ctx_batch_size`` rows; batch padding repeats row 0, whose
   duplicate KV writes are idempotent). Under ``chunked_prefill_config``
   a long prompt prefills ``chunk_size`` tokens per step through the
   prefix-prefill submodel, interleaving with other slots' decodes.
2. **Decode** every running slot in ONE batched TKG dispatch — rows carry
   their own positions and block tables / seq_ids, so a newly prefilled
   neighbor never disturbs an in-flight row (the continuous-batching
   property the integration tests pin token-for-token against per-prompt
   static ``generate``).
   With ``decode_steps_per_dispatch > 1`` compiled (contiguous layout),
   the engine dispatches a ``tkg_multistep`` window whenever no slot is
   within K tokens of its budget — in-scan EOS masking keeps mid-window
   finishes exact, and the rung choice guarantees fused steps never
   overshoot ``max_new_tokens``.
3. **Retire** finished slots (EOS / length): blocks freed, slot recycled
   for the next admission (the new request overwrites the line from
   position 0, so a dirty slot is safe by construction).

The plain decode step is two halves, ``_dispatch_decode`` and
``_collect_decode``. Where nothing needs a step's tokens on the host inside
that step (``_may_chain``), the engine leaves the dispatched program in
flight and, on the next step, dispatches step N+1 BEFORE it collects step N:
the next step's ids stay on the device (``decode_next_ids``), and scheduling,
packing, padding and the puts run beside the program instead of between two
of them. Otherwise the same two halves run the other way round (collect,
then dispatch), which is the order every step had before.

Preemption: when the paged pool cannot grow a running decode, the
scheduler evicts the youngest request back to WAITING (blocks freed); on
re-admission the engine re-prefills ``prompt + generated`` and the CTE's
sampled token is simply the next new token — token-exact under greedy
sampling (asserted across a forced preemption in the integration tests).

Telemetry rides the app's existing registry: ``nxdi_serve_queue_depth`` /
``nxdi_serve_slots_busy`` gauges, ``nxdi_serve_preemptions_total``
counter, and one request span per request covering
queue -> prefill -> decode with TTFT measured from arrival (under load it
includes queueing, as a serving TTFT should). On top of that the engine
owns a flight recorder (``telemetry/flight.py``: one StepRecord per
``step()`` with the step's time split by phase — schedule, kv, pack, pad,
enqueue, fetch, emit; ``Telemetry.phase`` opens each where the work happens,
as a ``nxdi.step.<phase>`` span of the profiler too — postmortem bundles on
SLO breach / preemption storm / retrace trip) and, when
``TpuConfig(slo=...)`` declares targets, an SLO tracker
(``telemetry/slo.py``: rolling attainment + SLO-conditioned goodput).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from nxdi_tpu.telemetry.tracing import (
    HOP_ENGINE_DECODE_FIRST,
    HOP_ENGINE_PREFILL,
    HOP_HANDOFF_EXPORT,
    HOP_HANDOFF_IMPORT,
    TraceContext,
)

from nxdi_tpu.runtime import faults
from nxdi_tpu.runtime.application import TAG_PREFIX_PREFILL
from nxdi_tpu.runtime.block_manager import BlockSpaceManager
from nxdi_tpu.runtime.model_wrapper import (
    MULTISTEP_EOS_SLOTS,
    TAG_CONTEXT_ENCODING,
    TAG_DEVICE_LOOP,
    TAG_MIXED,
    TAG_TOKEN_GENERATION,
    TAG_TOKEN_GENERATION_MULTISTEP,
    decode_window_limit,
)
from nxdi_tpu.ops.sampling import StepRngSchedule, extract_next_tokens
from nxdi_tpu.serving.request import (
    RUNNING,
    Request,
    RequestOutput,
    SamplingParams,
)
from nxdi_tpu.serving.scheduler import Scheduler, SchedulerConfig

logger = logging.getLogger("nxdi_tpu")

#: replica-fault marker (must match router.frontend.ENGINE_FAULT_PREFIX):
#: an error finish whose message starts with this is a replica-side crash
#: the router retries elsewhere — a validation rejection is not
ENGINE_FAULT_PREFIX = "engine step failed"

_NO_SPAN = contextlib.nullcontext()


@dataclasses.dataclass
class _InFlight:
    """One token-generation dispatch whose tokens are not on the host yet."""

    #: (slot, request, the request's ``preemptions`` at dispatch): a row is
    #: still its request's at collect only if the request is RUNNING and was
    #: not requeued since
    rows: List[Tuple[int, Request, int]]
    outputs: dict  # on the device, batch padding kept; ``tokens`` already on its way
    t0: float  # telemetry clock at dispatch (0.0 without telemetry)
    record: object  # the dispatching step's StepRecord, or None
    #: a row's token in flight is its last by ``max_new_tokens``: the next
    #: step collects before it schedules
    ends_a_row: bool


class InferenceEngine:
    """Host-side continuous-batching engine over a LOADED application.

    Supported KV layouts:

    - **paged** (``is_block_kv_layout``): slots are decode batch rows; a
      :class:`BlockSpaceManager` owns the pool, admission respects the
      free-block watermark, preemption on exhaustion.
    - **contiguous continuous batching** (``is_continuous_batching``): the
      slot index IS the ``seq_ids`` cache line; admission is slot-bounded
      (every line holds a full ``seq_len``, so decode growth cannot fail).

    **Threading model** (checked by :mod:`nxdi_tpu.analysis.concurrency`):
    the engine is *single-driver*. Exactly one thread — the ingest driver
    loop under ``cli.serve``, otherwise the caller's own — invokes
    ``add_request``/``step``/lifecycle methods, so the engine, its
    :class:`Scheduler`, the :class:`BlockSpaceManager`, and the handoff
    buffers deliberately own no locks. Cross-thread probes (the metrics
    HTTP plane, the router) never touch this state directly: they read
    through the FlightRecorder's and MetricsRegistry's locked snapshot
    surfaces, which is why those classes carry ``guarded_by`` annotations
    and this one does not.
    """

    def __init__(
        self,
        app,
        scheduler_config: Optional[SchedulerConfig] = None,
        seed: int = 0,
    ):
        if not getattr(app, "is_loaded", False):
            raise RuntimeError("InferenceEngine needs a loaded application")
        self.app = app
        tc = app.tpu_config
        self.tpu_config = tc
        if tc.on_device_sampling_config is None and not tc.output_logits:
            raise ValueError(
                "the engine needs token outputs: compile with "
                "on_device_sampling_config (or output_logits=True for host "
                "argmax)"
            )
        self.paged = bool(tc.is_block_kv_layout)
        # prefill/decode disaggregation role (serving/handoff.py): a
        # "prefill" engine parks each request after its first sampled token
        # and retains the KV chain until the router acks the handoff; a
        # "decode" engine admits requests ONLY as imported chains
        self.role = getattr(tc, "role", "unified")
        if not self.paged and not tc.is_continuous_batching:
            raise ValueError(
                "InferenceEngine drives the paged (is_block_kv_layout) or "
                "continuous-batching (is_continuous_batching) layouts; the "
                "static single-batch layout has no per-request cache routing "
                "— use HuggingFaceGenerationAdapter.generate instead"
            )
        self.telemetry = getattr(app, "telemetry", None)
        tel = self.telemetry if (self.telemetry and self.telemetry.enabled) else None
        # Telemetry.phase: the phases of a step, each opened where its work
        # happens (here: schedule, kv, pack, fetch, emit; the wrappers: pad,
        # enqueue); the shared null context when telemetry is off
        self._phase = tel.phase if tel is not None else (lambda _name: _NO_SPAN)

        # work on a copy: the resolved chunk_size below must not mutate a
        # caller-owned config (the Scheduler re-copies for the same reason)
        cfg = (
            dataclasses.replace(scheduler_config)
            if scheduler_config is not None
            else SchedulerConfig()
        )
        num_slots = (
            cfg.num_slots if cfg.num_slots is not None else tc.tkg_batch_size
        )
        if num_slots > tc.tkg_batch_size:
            raise ValueError(
                f"num_slots ({num_slots}) cannot exceed the compiled decode "
                f"batch (tkg_batch_size={tc.tkg_batch_size})"
            )
        if not self.paged:
            lines = tc.kv_cache_batch_size + tc.kv_cache_padding_size
            if num_slots > lines:
                raise ValueError(
                    f"num_slots ({num_slots}) cannot exceed the KV cache "
                    f"lines (kv_cache_batch_size + kv_cache_padding_size = "
                    f"{lines})"
                )
        self.block_manager = (
            BlockSpaceManager(tc.pa_num_blocks, tc.pa_block_size, telemetry=tel)
            if self.paged
            else None
        )
        # unified mixed dispatch: the whole step (prefill chunks + decode
        # rows) rides ONE packed mixed_model program; requires the app to
        # have compiled the submodel (TpuConfig(mixed_dispatch=True))
        self.mixed = bool(getattr(tc, "mixed_dispatch", False)) and getattr(
            app, "mixed_supported", False
        )
        self._mixed = app.models[TAG_MIXED] if self.mixed else None
        # device-resident decode loop: a decode window rides ONE
        # tkg_device_loop launch (lax.while_loop with per-row EOS/budget
        # exit in-graph) instead of per-token or per-rung dispatches;
        # requires the compiled submodel (TpuConfig(device_loop=True))
        self.device_loop = bool(getattr(tc, "device_loop", False)) and getattr(
            app, "device_loop_supported", False
        )
        self._dloop = app.models[TAG_DEVICE_LOOP] if self.device_loop else None
        self._loop_launches = None
        if self.device_loop and tel is not None:
            r = tel.registry
            self._loop_launches = r.counter(
                "nxdi_device_loop_launches_total",
                "device-resident decode loop launches per cap rung",
                ("cap",),
            )
            self._loop_iters_total = r.counter(
                "nxdi_device_loop_iterations_total",
                "while-loop iterations executed across launches per cap rung",
                ("cap",),
            )
            self._loop_tokens_total = r.counter(
                "nxdi_device_loop_tokens_total",
                "real tokens retired by device-loop launches per cap rung",
                ("cap",),
            )
            self._loop_tokens_per_dispatch = r.gauge(
                "nxdi_device_loop_tokens_per_dispatch",
                "real tokens retired by the LAST device-loop launch (the "
                "one-dispatch amortization the resident loop exists to buy)",
            )
        if cfg.chunk_size is None and tc.chunked_prefill_config is not None:
            cfg.chunk_size = tc.chunked_prefill_config.chunk_size
        if (
            cfg.chunk_size is not None
            and TAG_PREFIX_PREFILL not in app.models
            and not self.mixed
        ):
            # without a continuation submodel every multi-chunk prompt would
            # error-finish at its second chunk — even ones a single ordinary
            # CTE pass could have served; fail the misconfiguration loudly
            # at construction instead
            raise ValueError(
                f"chunk_size ({cfg.chunk_size}) needs a prefix-prefill "
                "submodel to continue chunks; compile the app with "
                "chunked_prefill_config (or is_prefix_caching)"
            )
        self.scheduler = Scheduler(
            num_slots, block_manager=self.block_manager, config=cfg, telemetry=tel
        )
        # radix prefix cache (serving/prefix_cache.py): retired sequences'
        # full KV blocks enter a radix tree; admissions fork the longest
        # cached prefix and prefill only the tail. Needs the paged layout
        # plus the ability to continue a prefill from a nonzero position
        # (prefix-prefill submodel or mixed dispatch).
        self.prefix_cache = None
        self._cow_counter = None
        if cfg.prefix_cache:
            if not self.paged:
                raise ValueError(
                    "prefix_cache requires the paged KV layout "
                    "(is_block_kv_layout=True)"
                )
            if TAG_PREFIX_PREFILL not in app.models and not self.mixed:
                raise ValueError(
                    "prefix_cache starts prefills at the cached position; "
                    "compile the app with is_prefix_caching (or "
                    "chunked_prefill_config) for the prefix-prefill "
                    "submodel, or with mixed_dispatch"
                )
            from nxdi_tpu.serving.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(self.block_manager, telemetry=tel)
            self.scheduler.prefix_cache = self.prefix_cache
        elif self.paged and tel is not None:
            # COW can fire without the cache (n>1 continuation forks), so
            # the counter family must exist either way
            self._cow_counter = tel.registry.counter(
                "nxdi_prefix_cow_copies",
                "private block copies materialized before a shared-block write",
            )
            self._cow_counter.inc(0)
        self.window_limit = decode_window_limit(tc, app.models)
        self._table_width = (
            -(-tc.seq_len // tc.pa_block_size) if self.paged else 0
        )
        self._rng = StepRngSchedule(seed)
        self._tkg = app.models[TAG_TOKEN_GENERATION]
        # counters of a model that holds a share of its experts; made on the
        # first step whose program returns the count (none for other models)
        self._moe_held_pairs = self._moe_routed_layer_steps = self._moe_routed_layers = None
        self._prefill_moe_held_pairs = self._prefill_moe_expert_rows = None
        self._sparse_blocks = None  # (read, live) counters of a block-sparse model
        self._kv_store = self._kv_window_rows = None  # ``_kv_held``, on first use
        self._can_continue_prefill = TAG_PREFIX_PREFILL in app.models
        #: the decode dispatch not collected yet (None: nothing in flight)
        self._inflight: Optional[_InFlight] = None
        self._t_collected = 0.0  # telemetry clock of the last collect
        # what of the configuration lets a dispatch stay in flight over the
        # step boundary: the tokens are sampled on the device and are all the
        # host reads of the step (no logits, captured tensors or logit_stats),
        # and the plain decode step is the only decode path compiled
        self._chain_compiled = (
            tc.on_device_sampling_config is not None
            and not tc.output_logits
            and getattr(tc, "tensor_capture_config", None) is None
            and not (self.mixed or self.device_loop)
            and not getattr(app, "multistep_supported", False)
        )
        self._chained_steps = self._overrun_tokens = None
        if tel is not None:
            self._chained_steps = tel.registry.counter(
                "nxdi_decode_chained_steps_total",
                "decode steps dispatched before the previous step's tokens were collected",
            )
            self._overrun_tokens = tel.registry.counter(
                "nxdi_decode_overrun_tokens_total",
                "tokens dispatched for a row that had left its slot by their "
                "collect (an EOS the host could not foresee), dropped unemitted",
            )
            self._chained_steps.inc(0)
            self._overrun_tokens.inc(0)
        # n>1 sibling forks also start their tail prefill mid-prompt, so
        # the scheduler may only fork when a continuation path is compiled
        self.scheduler.can_fork = self.paged and (
            self._can_continue_prefill or self.mixed
        )
        self._progress = False

        # flight recorder + SLO tracker (telemetry/flight.py, telemetry/
        # slo.py): the recorder journals every step() decision into a
        # bounded ring and fires postmortem bundles on SLO breach /
        # preemption storm / retrace-guard trip; the tracker turns declared
        # TpuConfig(slo=...) targets into rolling attainment gauges
        self.flight = None
        self.slo = None
        # QoS control plane, engine tier (control/qos.py): tenant quotas +
        # deadline-aware scheduling, attached below when TpuConfig(qos=...)
        # is declared alongside live telemetry (its slack math and bucket
        # refills ride the telemetry clock, so the two must share a domain)
        self.qos = None
        # numerics sentinel (telemetry/sentinel.py), attached at app.load()
        # when TpuConfig(sentinel=...) is declared: the engine adds the two
        # serving-only checks — the preemption-replay invariant on every
        # recompute-resume and the sampled shadow replay on retirement —
        # and (below, via attach_flight) binds its flight recorder so
        # numerics events capture postmortem bundles
        self.sentinel = tel.sentinel if tel is not None else None
        self._pending_breaches: List[Tuple[Request, List[str]]] = []
        if tel is not None:
            tc_tel = tc.telemetry
            if getattr(tc_tel, "flight", True):
                from nxdi_tpu.telemetry import FlightRecorder

                self.flight = FlightRecorder(
                    tel,
                    num_slots=num_slots,
                    max_records=getattr(tc_tel, "flight_records", 512),
                    postmortem_dir=getattr(tc_tel, "postmortem_dir", None),
                    storm_window=getattr(tc_tel, "storm_window", 32),
                    storm_preemptions=getattr(tc_tel, "storm_preemptions", 8),
                    state_fn=self.scheduler_state,
                    retrace_guard=getattr(app, "retrace_guard", None),
                )
                tel.attach_flight(self.flight)
                self.scheduler.flight = self.flight
            if getattr(tc, "slo", None) is not None:
                from nxdi_tpu.telemetry import SloTracker

                self.slo = SloTracker(tel, tc.slo)
                # every JSON snapshot (and so every postmortem bundle and
                # /snapshot probe) carries the targets-vs-measured readout
                tel.add_snapshot_extra("_slo", self.slo.to_dict)
        elif getattr(tc, "slo", None) is not None:
            logger.warning(
                "TpuConfig(slo=...) declared but telemetry is off — SLO "
                "attainment needs the request spans; nothing will be tracked"
            )
        if getattr(tc, "qos", None) is not None:
            if tel is not None and tel.enabled:
                from nxdi_tpu.control.qos import QosPolicy

                self.qos = QosPolicy(tc.qos, telemetry=tel)
                self.scheduler.qos = self.qos
            else:
                logger.warning(
                    "TpuConfig(qos=...) declared but telemetry is off — "
                    "quota buckets and deadline slack ride the telemetry "
                    "clock; QoS is disabled"
                )

        # fault tolerance (runtime/faults.py): taxonomy-driven step
        # recovery is always on (budgets from TpuConfig(faults=...)); the
        # dispatch watchdog is opt-in — it hops every dispatch through a
        # worker thread to bound it by the CostSheet-floor-derived timeout
        from nxdi_tpu.config import FaultConfig

        self.fault_config = getattr(tc, "faults", None) or FaultConfig()
        self._recovery_retries = None
        self._recovery_requeues = None
        self._recovery_fatal = None
        self._watchdog_trips = None
        if tel is not None:
            r = tel.registry
            self._recovery_retries = r.counter(
                "nxdi_recovery_retries_total",
                "in-place transient dispatch re-executions (watchdog retry)",
            )
            self._recovery_requeues = r.counter(
                "nxdi_recovery_requeues_total",
                "RUNNING requests requeued through the recompute-preemption "
                "path after a recoverable engine-step fault",
            )
            self._recovery_fatal = r.counter(
                "nxdi_recovery_fatal_total",
                "requests error-finished by fault recovery (fatal fault or "
                "recovery budget exhausted)",
            )
            self._watchdog_trips = r.counter(
                "nxdi_watchdog_trips_total",
                "dispatches abandoned by the watchdog timeout",
            )
            for c in (self._recovery_retries, self._recovery_requeues,
                      self._recovery_fatal, self._watchdog_trips):
                c.inc(0)
        self.watchdog = None
        fc = self.fault_config
        if fc.watchdog:
            self.watchdog = faults.DispatchWatchdog(
                multiplier=fc.watchdog_multiplier,
                min_timeout_s=fc.watchdog_min_timeout_s,
                max_retries=fc.max_retries,
                backoff_base_s=fc.backoff_base_s,
                backoff_max_s=fc.backoff_max_s,
                on_retry=(
                    self._recovery_retries.inc
                    if self._recovery_retries is not None else None
                ),
                on_trip=(
                    self._watchdog_trips.inc
                    if self._watchdog_trips is not None else None
                ),
            )
            self.watchdog.load_floors(app)
        #: requeue -> resumed-admission latencies (seconds) of step-fault
        #: recoveries; bench.py --serving --chaos reads it for the
        #: chaos_recovery_p95_ms headline
        self.recovery_resume_s: List[float] = []

        # -- KV handoff plane (prefill/decode disaggregation) --
        #: parked prefill-role requests: first token emitted, chain retained
        #: until the router's ack (request_id -> Request)
        self._handoffs: Dict[int, Request] = {}
        #: request_ids newly parked since the last ``take_ready_handoffs``
        self._handoff_ready: List[int] = []
        self._handoff_exports = None
        self._handoff_imports = None
        self._handoff_bytes = None
        if tel is not None and self.paged:
            r = tel.registry
            self._handoff_exports = r.counter(
                "nxdi_handoff_exports_total",
                "prefill-side KV chains exported for decode handoff",
            )
            self._handoff_imports = r.counter(
                "nxdi_handoff_imports_total",
                "decode-side KV chains imported and admitted RUNNING",
            )
            self._handoff_bytes = r.counter(
                "nxdi_handoff_bytes_total",
                "raw K/V bytes moved through the handoff plane",
            )
            if self.role != "unified":
                for c in (self._handoff_exports, self._handoff_imports,
                          self._handoff_bytes):
                    c.inc(0)

    # -- request intake -----------------------------------------------------
    def add_request(
        self,
        prompt: Sequence[int],
        params: Optional[SamplingParams] = None,
        on_token=None,
        request_id: Optional[int] = None,
        arrival_s: Optional[float] = None,
        session_id: Optional[str] = None,
        trace=None,
    ) -> Request:
        """Queue a request (WAITING). ``on_token(request, token)`` streams
        every generated token as it is sampled. ``arrival_s`` backdates the
        request's arrival for TTFT — it must be in the telemetry ``clock``
        domain (``time.perf_counter`` under the default clock).
        ``session_id`` is the conversation identity the router tier keys
        affinity on; it rides the request span. ``trace`` (optional
        :class:`~nxdi_tpu.telemetry.tracing.TraceContext`) is the request's
        distributed-trace position: engine-side hop spans (engine.prefill,
        handoff.export) parent under it and it rides the KV handoff wire."""
        if self.role == "decode":
            raise ValueError(
                "decode-role engine admits requests via KV handoff only "
                "(admit_handoff); route prompts to a prefill replica"
            )
        if params is not None and params.n > 1:
            # best-of-n: ONE prompt, n continuations. The primary request
            # prefills normally; each sibling is its own request that — on
            # the paged layout with a continuation path compiled — forks
            # the parent's committed prompt blocks at admission and
            # prefills only the last prompt token (sampling its own first
            # token), copy-on-writing the shared partial block on first
            # write. Elsewhere siblings degrade to plain re-prefills.
            base = dataclasses.replace(params, n=1)
            # the trace follows the PRIMARY only: one request, one trace —
            # sibling continuations are engine-internal fan-out
            primary = self.add_request(
                prompt, base, on_token=on_token, request_id=request_id,
                arrival_s=arrival_s, session_id=session_id, trace=trace,
            )
            for _ in range(params.n - 1):
                sib = self.add_request(
                    prompt, base, on_token=on_token,
                    arrival_s=primary.arrival_s, session_id=session_id,
                )
                if self.paged:
                    sib.fork_of = primary
                sib.fork_parent_id = primary.request_id
            return primary
        tel = self.telemetry
        if arrival_s is None and tel is not None and tel.enabled:
            # stamp arrival through the telemetry clock, not a hardcoded
            # perf_counter: under an injected clock the span's t_start must
            # share the domain first_token() subtracts it from
            arrival_s = tel.clock()
        req = Request(
            prompt, params=params, request_id=request_id, on_token=on_token,
            arrival_s=arrival_s, session_id=session_id, trace=trace,
        )
        # ids key the block tables: two LIVE requests sharing one would
        # decode through the same blocks (silent KV corruption) and
        # double-free on retirement. A user-supplied collision is rejected;
        # the auto counter catching up to a live user-chosen id just redraws
        # (that caller never asked for a specific id)
        live_ids = {r.request_id for r in self.scheduler.waiting}
        live_ids.update(r.request_id for r in self.scheduler.running())
        if request_id is None:
            while req.request_id in live_ids:
                req.request_id = next(Request._ids)
        elif req.request_id in live_ids:
            raise ValueError(
                f"request_id {req.request_id} is already live in the engine"
            )
        tc = self.tpu_config
        if len(req.prompt) >= self.window_limit:
            raise ValueError(
                f"prompt length {len(req.prompt)} leaves no decode room "
                f"inside the compiled window ({self.window_limit})"
            )
        if (
            len(req.prompt) > tc.max_context_length
            and self.scheduler.config.chunk_size is None
            and not self.mixed
        ):
            # mixed dispatch chunks inherently: any prompt too big for the
            # packed bucket budget simply continues next step
            raise ValueError(
                f"prompt length {len(req.prompt)} exceeds max_context_length "
                f"{tc.max_context_length} and chunked prefill is not "
                "configured (chunked_prefill_config)"
            )
        # clamp the budget to the compiled window, like the static adapter's
        # max_length = min(max_length, seq_len) — parity demands one rule
        budget = self.window_limit - len(req.prompt)
        if req.params.max_new_tokens > budget:
            req.params = dataclasses.replace(req.params, max_new_tokens=budget)
        if self.block_manager is not None:
            # reject up front what the pool can never hold even running
            # alone — otherwise the request livelocks through self-preempt/
            # resume cycles until the scheduler's never-fits guard trips and
            # takes the whole engine (and its neighbors) down with it
            bs = self.block_manager.block_size
            final = len(req.prompt) + req.params.max_new_tokens
            needed = -(-final // bs)
            if needed > self.block_manager.num_blocks:
                raise ValueError(
                    f"request needs {needed} KV blocks at its full length "
                    f"({final} tokens) but the pool holds "
                    f"{self.block_manager.num_blocks}; raise pa_num_blocks, "
                    "shorten the prompt, or lower max_new_tokens"
                )
        if self.qos is not None:
            # LAST gate, after every other validation: a request rejected
            # for a malformed shape must not consume tenant quota. Raises
            # QuotaExceeded (a ValueError) — the ingest tier's existing
            # error-finish conversion is what makes it a deterministic
            # 429-style finish instead of a crash.
            self.qos.admit(req)
        if tel is not None and tel.enabled:
            # backdate to the request's ARRIVAL: a driver submitting between
            # engine steps must not shave that wait off the reported TTFT
            req.span = tel.start_request(
                tokens_in=len(req.prompt), t_start=req.arrival_s,
                session_id=req.session_id, trace=req.trace,
            )
            req.span.phase("queue")
        self.scheduler.add(req)
        return req

    def _trace_hop(self, req: Request, hop: str, t0: Optional[float] = None,
                   attrs: Optional[dict] = None) -> None:
        """Record one engine-side hop span for a traced request, ending
        NOW, and advance the request's context so its next hop parents
        under this one. ``t0`` (wall clock) overrides the default start —
        the request's ``trace_t0`` stamp (admission / previous hop end)."""
        tel = self.telemetry
        tr = req.trace
        if tel is None or tr is None:
            return
        now = time.time()
        start = req.trace_t0 if t0 is None else t0
        if start is None:
            start = now
        sid = tel.record_hop(
            hop, tr, t_start=start, duration_s=now - start, attrs=attrs
        )
        if sid is not None:
            req.trace = tr.child(span_id=sid)
        req.trace_t0 = now

    # -- the engine loop ----------------------------------------------------
    def has_work(self) -> bool:
        if self._inflight is not None:
            return True  # its tokens are some request's; a step collects them
        if self._handoffs:
            # a parked handoff waits on the ROUTER's ack, not on a step —
            # only unparked occupants and queued work keep the loop hot
            busy = sum(
                1 for r in self.scheduler.slots
                if r is not None and r.request_id not in self._handoffs
            )
            return bool(self.scheduler.waiting) or busy > 0
        return self.scheduler.has_work()

    def step(self) -> List[RequestOutput]:
        """One engine iteration. Split dispatch (default): prefill work,
        then one batched decode. Mixed dispatch (``mixed_dispatch``): the
        step's prefill chunks AND decode rows ride ONE packed
        ``mixed_model`` program. Returns the requests that FINISHED during
        this step. With the flight recorder enabled every iteration
        journals one StepRecord (admissions, prefill chunks, the decode or
        mixed dispatch, preemptions, retirements, KV level, the time in each
        phase) and runs inside the profiler span ``nxdi.step`` of the same
        step number."""
        fl = self.flight
        if fl is None:
            return self._step(None)
        with self.telemetry.step_span(fl.begin_step().step):
            return self._step(fl)

    def _step(self, fl) -> List[RequestOutput]:
        finished: List[RequestOutput] = []
        try:
            if faults.ACTIVE_PLAN is not None:
                # failpoint "engine.step": a whole-step fault, upstream of
                # any dispatch — exercises the requeue recovery directly
                faults.fire(faults.SITE_ENGINE_STEP, self.telemetry)
            if self.mixed:
                self._step_mixed(finished)
            else:
                self._step_split(finished)
        except Exception as e:  # noqa: BLE001 — classified below
            kind = faults.classify(e)
            if kind == faults.KIND_FATAL:
                # the program or its inputs are broken: replaying would
                # reproduce the failure — escalate to the driver (the
                # ingest error-finishes with the engine-fault marker and
                # the router fails the work over to another replica)
                if self._recovery_fatal is not None:
                    self._recovery_fatal.inc()
                raise
            self._recover_step_fault(e, kind, finished)
        with self._phase("schedule"):
            self.scheduler.publish()
        if fl is not None:
            fl.end_step(
                self.scheduler.queue_depth,
                self.scheduler.slots_busy,
                self.block_manager.num_free_blocks()
                if self.block_manager is not None else None,
                kv_held=self._kv_held(),
            )
            # SLO-breach postmortems fire AFTER end_step so the bundle's
            # timeline includes the step the breaching request finished in
            pending, self._pending_breaches = self._pending_breaches, []
            for req, kinds in pending:
                fl.postmortem(
                    "slo_breach",
                    detail={"kinds": kinds},
                    request_span=req.span,
                    request_id=req.request_id,
                )
        return finished

    def _dispatch_guarded(self, tag: str, fn):
        """Run one dispatch closure, under the watchdog when armed. The
        closure captures batch + rng up front, so a watchdog retry replays
        the identical launch (same KV positions, same sampled values)."""
        if self.watchdog is not None:
            return self.watchdog.run(tag, fn)
        return fn()

    def _recover_step_fault(self, exc, kind: str, finished) -> None:
        """A recoverable (transient / exhausted) fault escaped the step:
        requeue every RUNNING request through the recompute-preemption
        path — the prompt+generated replay is token-exact under greedy
        (the PR-8 sentinel preemption-replay invariant) — instead of
        error-finishing the whole engine's work. A request over its
        ``max_recoveries`` budget error-finishes with the engine-fault
        marker so the router fails THAT request over individually."""
        fc = self.fault_config
        clock = self.telemetry.clock if self.telemetry is not None else None
        # a dispatch in flight: the tokens the device has made are emitted
        # before their rows requeue (the replay starts after them); if the
        # fault took them too they are dropped, and the replay of
        # prompt + generated makes them again
        try:
            self._drain(finished)
        except Exception as e:  # noqa: BLE001 — the fault being recovered
            logger.warning("dropped the decode in flight: %s", e)
            self._inflight = None
        victims = [r for r in self.scheduler.slots if r is not None]
        logger.warning(
            "engine step fault (%s), recovering %d running request(s): %s",
            kind, len(victims), exc,
        )
        requeued = failed = 0
        for req in victims:
            req.recoveries += 1
            if req.recoveries > fc.max_recoveries:
                req.error = (
                    f"{ENGINE_FAULT_PREFIX}: {exc} (recovery budget "
                    f"exhausted after {fc.max_recoveries})"
                )
                if self._recovery_fatal is not None:
                    self._recovery_fatal.inc()
                failed += 1
                span = req.span
                self._finish(req, "error", finished)
                if self.flight is not None:
                    self.flight.postmortem(
                        "fault_recovery",
                        detail={
                            "kind": kind, "error": str(exc),
                            "recoveries": req.recoveries,
                            "max_recoveries": fc.max_recoveries,
                        },
                        request_span=span,
                        request_id=req.request_id,
                    )
            else:
                if clock is not None:
                    req._recovered_at = clock()
                self.scheduler._preempt(req)
                requeued += 1
                if self._recovery_requeues is not None:
                    self._recovery_requeues.inc()
        if self.flight is not None:
            self.flight.record_fault(kind, str(exc), requeued, failed)
        # the requeues freed blocks and reshaped the queue — that IS the
        # progress that lets the next step readmit; never trip the stall
        # guard for a recovered fault
        self._progress = True

    def _note_resumes(self, prefills: List[Request]) -> None:
        """Stamp requeue -> resumed-admission latency for requests that
        re-entered a slot after a step-fault recovery."""
        clock = self.telemetry.clock if self.telemetry is not None else None
        for req in prefills:
            t = req._recovered_at
            if t is not None:
                req._recovered_at = None
                if clock is not None:
                    self.recovery_resume_s.append(clock() - t)

    def _step_split(self, finished: List[RequestOutput]) -> None:
        """The classic two-phase step: per-request prefill dispatches, then
        one batched decode dispatch; the previous step's decode is collected
        first or after it (``_may_chain``)."""
        phase = self._phase
        preempted: List[Request] = []
        flight = self._inflight
        if flight is not None and (flight.ends_a_row or not self._may_chain()):
            # a finish by max_new_tokens is known before its token is here:
            # collected first, it frees the slot and the blocks for this
            # step's admission, and every dispatch has the rows and the rng
            # draw it has in the synchronous order
            self._drain(finished)
        with phase("schedule"):
            if self.role == "decode" and self.scheduler.waiting:
                # a decode-role engine compiles no prefill program: anything
                # in the waiting queue (a preempted import) cannot be
                # replayed locally — error-finish with the engine-fault
                # marker so the router re-routes it through a prefill replica
                # (prompt replay + fresh handoff; greedy tokens are
                # identical, delivered ones are cursor-skipped)
                while self.scheduler.waiting:
                    req = self.scheduler.waiting.popleft()
                    req.error = (
                        f"{ENGINE_FAULT_PREFIX}: decode-role replica cannot "
                        "re-prefill a preempted request"
                    )
                    self._finish(req, "error", finished)
            prefills = self.scheduler.schedule_prefills()
            self._note_resumes(prefills)
        for req in prefills:
            self._prefill_chunk(req, finished)
        with phase("schedule"):
            rows = self._decodable()
        if rows and self._inflight is not None and not self._growth_fits(rows):
            # the pool cannot grow these rows without a preemption, and a
            # victim's replay starts from the tokens the host holds: collect
            # first, then preempt as ever
            self._drain(finished)
            with phase("schedule"):
                rows = self._decodable()
        if rows:
            with phase("kv"):
                rows, preempted = self.scheduler.ensure_decode_capacity(rows)
                for victim in preempted:
                    logger.info(
                        "preempted request %d (recompute on re-admission)",
                        victim.request_id,
                    )
                rows = self._cow_decode_rows(rows)
        if rows:
            with phase("schedule"):
                loop = self._use_device_loop(rows)
                steps = 1 if loop else self._choose_steps(rows)
            if loop:
                self._decode_device_loop(rows, finished)
            elif steps > 1:
                self._decode_multistep(rows, steps, finished)
            else:
                self._decode_single(rows, finished)
        else:
            self._drain(finished)  # nothing to dispatch ahead of it
        # a preemption-only step still made progress (the freed blocks are
        # what lets the NEXT step admit) — only a true no-op step may trip
        # the stall guard in run()
        self._progress = (
            bool(prefills) or bool(rows) or bool(preempted) or bool(finished)
            or flight is not None
        )

    def _decodable(self) -> List[Tuple[int, Request]]:
        rows = self.scheduler.decodable()
        if self._handoffs and rows:
            # parked prefill-role requests hold their slot/chain for
            # export; they never join a decode batch
            rows = [
                (s, r) for s, r in rows if r.request_id not in self._handoffs
            ]
        return rows

    def _step_mixed(self, finished: List[RequestOutput]) -> None:
        """One-dispatch mixed step: pack this step's prefill chunks and
        every decode row into ONE flat token stream and serve it with a
        single ``mixed_model`` dispatch (the ragged paged-attention
        program). Chunking IS the packing policy — whatever part of a
        prompt does not fit the remaining bucket budget continues next
        step — so chunked prefill needs no separate admission path and no
        prefix-prefill submodel."""
        tc = self.tpu_config
        phase = self._phase
        preempted: List[Request] = []
        with phase("schedule"):
            prefills = self.scheduler.schedule_prefills()
            self._note_resumes(prefills)
            rows = self.scheduler.decodable()
        if rows:
            # grow every decode row's table BEFORE packing: a preemption
            # must evict its victim from THIS step's packed batch, never
            # fault mid-dispatch. The victim may be a request admitted just
            # above — the state filter below drops it from the pack.
            with phase("kv"):
                rows, preempted = self.scheduler.ensure_decode_capacity(rows)
                for victim in preempted:
                    logger.info(
                        "preempted request %d (recompute on re-admission)",
                        victim.request_id,
                    )
                rows = self._cow_decode_rows(rows)
        prefills = [r for r in prefills if r.state == RUNNING]

        w = self._mixed
        budget = w.buckets[-1] - len(rows)  # decode singles ride along
        limit = self.scheduler.config.chunk_size or tc.max_context_length
        tokens: List[int] = []
        positions: List[int] = []
        row_ids: List[int] = []
        packed_prefills: List[Tuple[Request, int]] = []  # (req, chunk len)
        for req in prefills:
            room = min(limit, budget)
            if room <= 0:
                continue  # bucket full; this chunk continues next step
            start = req.num_prefilled
            chunk = req.seq_tokens[: req.prefill_target][start : start + room]
            if not chunk:
                continue
            with phase("kv"):
                try:
                    self._cow_for_write(req, start, start + len(chunk))
                except RuntimeError:
                    logger.info(
                        "preempted request %d: no block for its COW copy",
                        req.request_id,
                    )
                    self.scheduler._preempt(req)
                    continue
            with phase("pack"):
                tokens.extend(chunk)
                positions.extend(range(start, start + len(chunk)))
                row_ids.extend([req.slot] * len(chunk))
                packed_prefills.append((req, len(chunk)))
                budget -= len(chunk)
        self._progress = bool(packed_prefills) or bool(rows) or bool(preempted)
        if not packed_prefills and not rows:
            return

        with phase("pack"):
            for slot, req in rows:
                tokens.append(req.generated[-1])
                positions.append(req.total_len - 1)
                row_ids.append(slot)
            R = tc.tkg_batch_size
            wt = self._table_width
            bs = tc.pa_block_size
            total = len(tokens)
            bt = np.full((R, wt), -1, dtype=np.int32)
            lti = np.zeros((R,), dtype=np.int32)
            params_rows: List[Optional[SamplingParams]] = [None] * R
            tables: Dict[int, np.ndarray] = {}
            by_slot: Dict[int, Request] = {req.slot: req for req, _ in packed_prefills}
            by_slot.update({slot: req for slot, req in rows})
            for slot, req in by_slot.items():
                table = np.asarray(
                    self.block_manager.block_table(req.request_id, wt),
                    dtype=np.int32,
                )
                tables[slot] = table
                bt[slot] = table
                params_rows[slot] = req.params
            sm = np.empty((total,), dtype=np.int32)
            for t, (slot, p) in enumerate(zip(row_ids, positions)):
                entry = int(tables[slot][p // bs])
                sm[t] = entry * bs + p % bs if entry >= 0 else -1
                lti[slot] = t  # per-row tokens are packed ascending: last wins

            kwargs: Dict[str, np.ndarray] = {
                "block_table": bt.reshape(1, R * wt),
                "slot_mapping": sm[None, :],
                "mixed_row_ids": np.asarray(row_ids, dtype=np.int32)[None, :],
            }
            if w.needs_rng:
                kwargs["rng"] = self._rng.next()
            bucket = w.select_bucket(total)
            if self.flight is not None:
                self.flight.record_mixed(
                    TAG_MIXED, bucket, len(packed_prefills), len(rows),
                    total, bucket,
                )
                for req, n in packed_prefills:
                    self.flight.record_prefill(
                        req.request_id, req.slot, TAG_MIXED, req.num_prefilled, n
                    )
            ids = np.asarray(tokens, dtype=np.int32)[None, :]
            pos = np.asarray(positions, dtype=np.int32)[None, :]
            sampling = SamplingParams.rows_tensor(
                [p if p is not None else SamplingParams() for p in params_rows]
            )
        clock = self.telemetry.clock if self.telemetry is not None else None
        t0 = clock() if clock else 0.0
        out = self._dispatch_guarded(
            TAG_MIXED,
            lambda: self.app.forward(
                ids, pos, last_token_index=lti, sampling_params=sampling,
                submodel=TAG_MIXED, **kwargs,
            ),
        )
        with phase("fetch"):
            toks = self._tokens_of(out)  # (R,): one per slot; idle rows garbage
        dt = (clock() - t0) if clock else None

        with phase("emit"):
            for req, n in packed_prefills:
                req.num_prefilled += n
                if not req.prefill_done:
                    continue  # more chunks next step; decodes keep interleaving
                self.scheduler.note_prefill_complete(req)
                if (
                    self.sentinel is not None
                    and self.sentinel.config.preemption_check
                    and req.preemptions > 0
                    and req.generated
                ):
                    # preemption-replay invariant, same as the split path
                    self.sentinel.verify_replay(req, "preemption")
                if req.span is not None:
                    req.span.first_token()
                    req.span.phase("decode")
                    req.span.tokens(1)
                self._trace_hop(req, HOP_ENGINE_PREFILL)
                req.emit(int(toks[req.slot]))
                reason = req.check_finish()
                if reason:
                    self._finish(req, reason, finished)
            for slot, req in rows:
                if req.span is not None:
                    req.span.tokens(1, dt)
                req.emit(int(toks[slot]))
                reason = req.check_finish()
                if reason:
                    self._finish(req, reason, finished)

    def run(self, max_steps: Optional[int] = None) -> List[RequestOutput]:
        """Step until every queued request finishes; returns all outputs."""
        outputs: List[RequestOutput] = []
        n = 0
        while self.has_work():
            if max_steps is not None and n >= max_steps:
                break
            outputs.extend(self.step())
            n += 1
            if not self._progress and self.has_work():
                raise RuntimeError(
                    "scheduler stalled: requests waiting but nothing "
                    "admissible or decodable (KV pool too small for the "
                    "queued work?)"
                )
        return outputs

    # -- copy-on-write ------------------------------------------------------
    def _cow_for_write(self, req: Request, lo: int, hi: int) -> None:
        """Before ``req`` writes KV for positions ``[lo, hi)``, give it a
        private copy of every SHARED block the range touches (refcount > 1:
        a prefix-cache chain or an ``n > 1`` fork still holds it). The
        manager swaps the table entry (``cow_block``); the data moves on
        device (``copy_kv_blocks``). Full-block cache hits never trigger
        this — the uncached tail starts block-aligned — so in practice it
        fires on the partial prompt block an n-fork shares."""
        mgr = self.block_manager
        if mgr is None or hi <= lo:
            return
        table = mgr._tables.get(req.request_id)
        if not table:
            return
        bs = mgr.block_size
        src: List[int] = []
        dst: List[int] = []
        for bi in range(lo // bs, min((hi - 1) // bs, len(table) - 1) + 1):
            if mgr._refs[table[bi]] > 1:
                s, d = mgr.cow_block(req.request_id, bi)
                src.append(s)
                dst.append(d)
        if src:
            from nxdi_tpu.kvcache.kv_cache import copy_kv_blocks

            self.app.kv_cache = copy_kv_blocks(self.app.kv_cache, src, dst, bs)
            if self.prefix_cache is not None:
                self.prefix_cache.note_cow(len(src))
            elif self._cow_counter is not None:
                self._cow_counter.inc(len(src))

    def _cow_decode_rows(
        self, rows: List[Tuple[int, Request]]
    ) -> List[Tuple[int, Request]]:
        """COW each decode row's next write position. A row whose private
        copy cannot be allocated (pool truly dry even after cache
        eviction) is preempted instead of faulting the whole step."""
        if self.block_manager is None:
            return rows
        kept: List[Tuple[int, Request]] = []
        for slot, req in rows:
            try:
                self._cow_for_write(req, req.dispatched_len - 1, req.dispatched_len)
                kept.append((slot, req))
            except RuntimeError:
                logger.info(
                    "preempted request %d: no block for its COW copy",
                    req.request_id,
                )
                self.scheduler._preempt(req)
        return kept

    # -- prefill ------------------------------------------------------------
    def _prefill_chunk(self, req: Request, finished: List[RequestOutput]) -> None:
        phase = self._phase
        with phase("pack"):
            seq = req.seq_tokens[: req.prefill_target]
            start = req.num_prefilled
            limit = (
                self.scheduler.config.chunk_size or self.tpu_config.max_context_length
            )
            chunk = seq[start : start + limit]
            n = len(chunk)
        if len(seq) > limit and not self._can_continue_prefill:
            # a preempted request's prompt+generated replay outgrew the one
            # CTE pass and no prefix/chunked submodel is compiled to continue
            # it — fail THIS request (before dispatching a truncated, wrong-
            # content prefill), not the engine: its neighbors keep serving
            logger.warning(
                "request %d cannot resume: its %d-token re-prefill exceeds "
                "max_context_length %d and no prefix-prefill submodel is "
                "compiled (enable chunked_prefill_config or is_prefix_caching)",
                req.request_id, len(seq), self.tpu_config.max_context_length,
            )
            self._finish(req, "error", finished)
            return
        with phase("kv"):
            try:
                self._cow_for_write(req, start, start + n)
            except RuntimeError:
                # pool dry even after cache eviction: requeue rather than fault
                logger.info(
                    "preempted request %d: no block for its COW copy",
                    req.request_id,
                )
                self.scheduler._preempt(req)
                return
        with phase("pack"):
            ids = np.asarray([chunk], dtype=np.int32)
            pos = (start + np.arange(n, dtype=np.int32))[None, :]
            kwargs = self._layout_kwargs([(req.slot, req)])
            self._maybe_rng(kwargs)
            submodel = TAG_CONTEXT_ENCODING if start == 0 else TAG_PREFIX_PREFILL
            last = np.array([n - 1], dtype=np.int32)
            sampling = req.params.tensor(1)
        out = self._dispatch_guarded(
            submodel,
            lambda: self.app.forward(
                ids, pos, last_token_index=last, sampling_params=sampling,
                submodel=submodel, **kwargs,
            ),
        )
        if self.flight is not None:
            self.flight.record_prefill(
                req.request_id, req.slot, submodel, start, n
            )
        req.num_prefilled += n
        if not req.prefill_done:
            return  # more chunks next step; decodes interleave meanwhile
        self.scheduler.note_prefill_complete(req)
        if (
            self.sentinel is not None
            and self.sentinel.config.preemption_check
            and req.preemptions > 0
            and req.generated
        ):
            # preemption-replay invariant: the prompt+generated replay this
            # (re)prefill just committed must reproduce the pre-preemption
            # tokens exactly — verified through the independent logit probe;
            # a mismatch counts nxdi_sentinel_replay_mismatch_total
            # {kind="preemption"} and bundles instead of silently serving a
            # forked continuation
            self.sentinel.verify_replay(req, "preemption")
        with phase("fetch"):
            tok = int(self._tokens_of(out)[0])
            compact = None
            if "prefill_moe_held_pairs" in out and (
                self.flight is not None or self.telemetry is not None
            ):
                compact = int(out["prefill_moe_held_pairs"]), int(out["prefill_moe_expert_rows"])
        if compact is not None:
            self._note_prefill_moe(*compact)
        with phase("emit"):
            if req.span is not None:
                req.span.first_token()  # idempotent: a resume keeps the original
                req.span.phase("decode")
                req.span.tokens(1)
            self._trace_hop(req, HOP_ENGINE_PREFILL)
            req.emit(tok)
            reason = req.check_finish()
            if reason:
                self._finish(req, reason, finished)
            elif self.role == "prefill":
                self._park_for_handoff(req)

    # -- KV handoff plane (prefill/decode disaggregation) -------------------
    def _park_for_handoff(self, req: Request) -> None:
        """Prefill role: the first token is sampled and streamed; instead of
        decoding on, hold the request in its slot — blocks pinned, excluded
        from decode batches and victim selection — until the router exports
        the chain and acks a decode-side import."""
        self._handoffs[req.request_id] = req
        self._handoff_ready.append(req.request_id)
        self.scheduler.unpreemptible.add(req.request_id)
        if req.span is not None:
            req.span.phase("handoff")

    def take_ready_handoffs(self) -> List[int]:
        """Request ids newly parked since the last call (ingest driver poll)."""
        out, self._handoff_ready = self._handoff_ready, []
        return out

    def export_handoff(self, request_id: int):
        """Build the wire payload for a parked request. The chain stays
        parked — re-exportable — until :meth:`ack_handoff`."""
        from nxdi_tpu.kvcache import export_kv_blocks
        from nxdi_tpu.serving.handoff import HandoffPayload

        t0 = time.time()
        req = self._handoffs.get(request_id)
        if req is None:
            raise KeyError(f"request {request_id} is not parked for handoff")
        mgr = self.block_manager
        bs = mgr.block_size
        committed = req.prefill_target
        n_blocks = -(-committed // bs)
        table = mgr._tables.get(req.request_id, [])[:n_blocks]
        if len(table) < n_blocks:
            raise RuntimeError(
                f"parked request {request_id} holds {len(table)} blocks but "
                f"its committed prefill needs {n_blocks}"
            )
        kv = export_kv_blocks(self.app.kv_cache, table, bs)
        payload = HandoffPayload(
            request_id=req.request_id,
            prompt=list(req.prompt),
            first_tokens=list(req.generated),
            committed=committed,
            sampling=HandoffPayload.sampling_wire(req.params),
            rng_seed=self._rng.seed,
            rng_counter=self._rng.counter,
            block_size=bs,
            dtype=str(np.asarray(kv["k"]).dtype),
            kv=kv,
            session_id=req.session_id,
        )
        if self._handoff_exports is not None:
            self._handoff_exports.inc()
            self._handoff_bytes.inc(payload.nbytes)
        # export hop covers the payload build; the wire then carries the
        # advanced context so the decode side's import hop parents under it
        # (a re-export after a failed import re-stamps — last export wins,
        # matching which decode replica actually continued the request)
        self._trace_hop(req, HOP_HANDOFF_EXPORT, t0=t0,
                        attrs={"bytes": payload.nbytes})
        if req.trace is not None:
            payload.trace = req.trace.to_dict()
        return payload

    def ack_handoff(self, request_id: int) -> None:
        """The router confirmed a decode replica imported the chain: retire
        the parked request (its committed blocks enter the prefix cache
        before the pool reclaims them) and recycle the slot."""
        req = self._handoffs.pop(request_id, None)
        if req is None:
            raise KeyError(f"request {request_id} is not parked for handoff")
        self.scheduler.unpreemptible.discard(request_id)
        slot = req.slot
        if req.span is not None:
            req.span.finish()
        self.scheduler.retire(req, "handoff")
        if self.flight is not None:
            self.flight.record_retirement(req.request_id, slot, "handoff")

    def admit_handoff(self, payload, on_token=None) -> Request:
        """Decode-side admission: validate the payload against this cache's
        format, place the chain into the block pool, and enter the request
        directly RUNNING in decode state — no local prefill ever runs.
        Raises ``ValueError`` on a deterministic format mismatch and
        :class:`~nxdi_tpu.serving.handoff.HandoffCapacityError` when a slot
        or the pool has no room right now (transient: the router re-ranks
        and tries the next decode replica)."""
        from nxdi_tpu.kvcache import import_kv_blocks
        from nxdi_tpu.serving.handoff import HandoffCapacityError

        t0 = time.time()
        if not self.paged:
            raise ValueError("admit_handoff requires the paged KV layout")
        mgr = self.block_manager
        payload.validate_against(mgr.block_size, self.app.kv_cache["k"].dtype)
        sch = self.scheduler
        slot = sch._free_slot()
        if slot is None:
            raise HandoffCapacityError("no free engine slot for the import")
        params = payload.sampling_params()
        req = Request(
            payload.prompt, params=params, on_token=on_token,
            session_id=payload.session_id,
        )
        live_ids = {r.request_id for r in sch.waiting}
        live_ids.update(r.request_id for r in sch.running())
        req.request_id = payload.request_id
        while req.request_id in live_ids:
            req.request_id = next(Request._ids)
        if payload.committed + max(params.max_new_tokens, 1) > self.window_limit:
            # same budget clamp as add_request: one rule on both roles keeps
            # greedy parity with the unified engine
            budget = self.window_limit - payload.committed
            if budget < 1:
                raise ValueError(
                    f"imported chain ({payload.committed} committed tokens) "
                    f"leaves no decode room in the compiled window "
                    f"({self.window_limit})"
                )
            req.params = dataclasses.replace(req.params, max_new_tokens=budget)
        committed = payload.committed
        n_blocks = -(-committed // mgr.block_size)
        free = mgr.num_free_blocks()
        headroom = sch.config.watermark_blocks or 0
        if free - n_blocks < (headroom if sch.slots_busy else 0):
            raise HandoffCapacityError(
                f"pool pressure: import needs {n_blocks} blocks, "
                f"{free} free (watermark {headroom})"
            )
        try:
            table = mgr.ensure_capacity(req.request_id, committed)
        except RuntimeError as e:
            mgr.free_seq(req.request_id)
            raise HandoffCapacityError(str(e)) from e
        try:
            self.app.kv_cache = import_kv_blocks(
                self.app.kv_cache, table[:n_blocks], payload.kv, mgr.block_size
            )
        except Exception:
            mgr.free_seq(req.request_id)
            raise
        # seed the already-streamed tokens WITHOUT re-firing on_token: the
        # prefill side delivered them; the decode side's stream continues
        # from its cursor
        req.generated = [int(t) for t in payload.first_tokens]
        sch.place_imported(req, slot, committed)
        # continue the prefill side's trace: the wire context's span_id is
        # the exporting replica's handoff.export hop, so this replica's
        # import/decode hops land as its children in the assembled tree
        req.trace = TraceContext.from_dict(payload.trace) \
            if payload.trace is not None else None
        req.trace_t0 = t0
        self._trace_hop(req, HOP_HANDOFF_IMPORT, t0=t0,
                        attrs={"bytes": payload.nbytes})
        # the handed-off first token is available to the client the moment
        # the import commits — near-zero duration by construction; residual
        # delivery time is the router's stream.deliver hop
        self._trace_hop(req, HOP_ENGINE_DECODE_FIRST,
                        attrs={"seeded_tokens": len(req.generated)})
        tel = self.telemetry
        if tel is not None and tel.enabled:
            req.span = tel.start_request(
                tokens_in=len(req.prompt), session_id=req.session_id,
                trace=req.trace,
            )
            req.span.first_token()
            req.span.phase("decode")
            req.span.tokens(len(req.generated))
        if self._handoff_imports is not None:
            self._handoff_imports.inc()
            self._handoff_bytes.inc(payload.nbytes)
        if self.flight is not None:
            self.flight.record_admission(
                req.request_id, slot, resumed=False,
                cached_tokens=committed, total_tokens=req.total_len,
            )
        return req

    # -- decode -------------------------------------------------------------
    def _choose_steps(self, rows: List[Tuple[int, Request]]) -> int:
        """Pick the multistep rung for this window. The in-scan per-row
        ``budget_steps`` mask lets rows near ``max_new_tokens`` join a
        window — they freeze in-graph after their last real token (KV
        write dropped, position pinned) and the host discards the pad
        tail — so the rung no longer clamps to the MINIMUM remaining
        budget. What remains: every row's LAST real write must stay
        inside the compiled decode window (per-row math, since a row only
        advances min(remaining, rung) steps), and rows with more EOS ids
        than the compiled slots force single-step."""
        if not getattr(self.app, "multistep_supported", False):
            return 1
        if any(
            len(r.params.eos_token_ids) > MULTISTEP_EOS_SLOTS for _, r in rows
        ):
            return 1
        w = self.app.models[TAG_TOKEN_GENERATION_MULTISTEP]
        max_rem = max(r.remaining for _, r in rows)
        if max_rem <= 1:
            return 1

        def window_ok(s: int) -> bool:
            return all(
                r.total_len + min(r.remaining, s) <= self.window_limit + 1
                for _, r in rows
            )

        rungs = [s for s in w.steps_ladder if window_ok(s)]
        if not rungs:
            return 1
        covering = [s for s in rungs if s >= max_rem]
        # the smallest rung that finishes EVERY row beats the biggest rung
        # that scans (and then discards) a frozen tail
        return min(covering) if covering else max(rungs)

    def _layout_kwargs(
        self, rows: List[Tuple[int, Request]]
    ) -> Dict[str, np.ndarray]:
        if self.paged:
            bt = np.stack(
                [
                    self.block_manager.block_table(r.request_id, self._table_width)
                    for _, r in rows
                ]
            )
            if not self._tkg.per_slot_cache:
                return {"block_table": bt}
            # a store held per slot beside the pool is addressed by slot id
            return {"block_table": bt, "seq_ids": self._slot_ids(rows)}
        return {"seq_ids": self._slot_ids(rows)}

    def _kv_held(self) -> Optional[Tuple[int, int, int]]:
        """(live tokens, ring rows held, bytes held) of a cache tree with a
        store per slot beside the pool, as the arrays store them; the gauge
        ``nxdi_kv_window_rows_held`` follows. None for every other tree."""
        if not (self.paged and self._tkg.per_slot_cache):
            return None
        if self._kv_store is None:
            cache, mgr = self.app.kv_cache, self.block_manager
            per_slot = [a for name, a in cache.items() if name not in ("k", "v")]
            self._kv_store = (
                # rows a slot: ring rows (L, slots, KV, rows, D), or the axis the architecture names
                per_slot[0].shape[getattr(self._tkg.arch, "slot_rows_axis", 3)],
                sum(a.nbytes // a.shape[1] for a in per_slot),  # bytes a slot, each store its own
                (cache["k"].nbytes + cache["v"].nbytes) // mgr.num_blocks,
            )
            tel = self.telemetry
            if tel is not None and tel.enabled:
                self._kv_window_rows = tel.registry.gauge(
                    "nxdi_kv_window_rows_held",
                    "ring rows of the per-slot window store held by seated requests",
                )
        rows_a_slot, slot_bytes, block_bytes = self._kv_store
        running = self.scheduler.running()
        mgr = self.block_manager
        held = len(running) * rows_a_slot
        if self._kv_window_rows is not None:
            self._kv_window_rows.set(held)
        return (
            sum(r.total_len for r in running),
            held,
            (mgr.num_blocks - mgr.num_free_blocks()) * block_bytes + len(running) * slot_bytes,
        )

    @staticmethod
    def _slot_ids(rows: List[Tuple[int, Request]]) -> np.ndarray:
        return np.array([slot for slot, _ in rows], dtype=np.int32)

    def _maybe_rng(self, kwargs: Dict[str, np.ndarray]) -> None:
        if self._tkg.needs_rng:
            kwargs["rng"] = self._rng.next()

    def _may_chain(self) -> bool:
        """Whether a decode dispatch may stay in flight over the step
        boundary, from what the engine sees now; no option selects it. Not
        when something reads the step's outputs on the host inside the step:
        a synchronous dispatch (``detail="full"``, a profiler's post hooks),
        an input snapshot, the numerics sentinel's ``logit_stats`` and
        replays; and not where the configuration rules it out
        (``_chain_compiled``)."""
        if not self._chain_compiled or self.sentinel is not None:
            return False
        tkg, tel = self._tkg, self.telemetry
        if tkg.post_hooks or tkg.snapshot_hook is not None:
            return False
        return not (tel is not None and tel.enabled and tel.sync_dispatch)

    def _growth_fits(self, rows: List[Tuple[int, Request]]) -> bool:
        """Whether the pool grows (and copies on write) every row's next
        position with no preemption."""
        mgr = self.block_manager
        if mgr is None or mgr.num_free_blocks() >= len(rows):
            return True  # a row takes one block at the most: a new one, or a copy
        bs = mgr.block_size
        need = 0
        for _, r in rows:
            new = mgr.blocks_needed(r.request_id, r.dispatched_len)
            need += new
            table = mgr._tables.get(r.request_id)
            bi = (r.dispatched_len - 1) // bs
            if not new and table and bi < len(table) and mgr._refs[table[bi]] > 1:
                need += 1  # a shared block is copied before the write
        return need <= mgr.num_free_blocks()

    def _drain(self, finished: List[RequestOutput]) -> None:
        """Collect the decode in flight, if any."""
        flight, self._inflight = self._inflight, None
        if flight is not None:
            self._collect_decode(flight, finished)

    def _decode_single(
        self, rows: List[Tuple[int, Request]], finished: List[RequestOutput]
    ) -> None:
        """The plain decode step: dispatch this step's program, collect the
        previous step's tokens. With nothing left in flight (the chain is
        not kept, or was drained earlier in the step) there is nothing to
        collect after the dispatch; where the chain may not start either,
        this step's own tokens are collected at once: the same two halves
        in the synchronous order."""
        prev = self._inflight
        self._inflight = self._dispatch_decode(rows, prev)
        if prev is not None:
            self._collect_decode(prev, finished)
        elif not self._may_chain():
            self._drain(finished)

    def _dispatch_decode(
        self, rows: List[Tuple[int, Request]], prev: Optional[_InFlight]
    ) -> _InFlight:
        """Dispatch half: pack, pad, put and enqueue one token-generation
        program for ``rows`` and start its tokens' copy to the host. A row
        that is in ``prev`` (its token is dispatched, not yet emitted) takes
        its input id from ``prev``'s tokens on the device; positions, block
        table, slot mapping, sampling rows and rng depend on counts only
        (``Request.dispatched_len``) and are host-built."""
        phase = self._phase
        fl = self.flight
        with phase("pack"):
            B = len(rows)
            batch = self._layout_kwargs(rows)
            if prev is not None:
                row_of = {id(r): i for i, (_, r, _) in enumerate(prev.rows)}
                batch["prev_tokens"] = prev.outputs["tokens"]
                batch["prev_rows"] = np.array(
                    [row_of[id(r)] if r.pending else -1 for _, r in rows],
                    dtype=np.int32,
                )
            ids = np.array(
                [[0 if r.pending else r.generated[-1]] for _, r in rows],
                dtype=np.int32,
            )
            pos = np.array([[r.dispatched_len - 1] for _, r in rows], dtype=np.int32)
            self._maybe_rng(batch)
            last = np.zeros((B,), dtype=np.int32)
            sampling = SamplingParams.rows_tensor([r.params for _, r in rows])
            if fl is not None:
                fl.record_decode(
                    TAG_TOKEN_GENERATION, 1, rows, self.tpu_config.tkg_batch_size,
                    chained=prev is not None,
                )
        clock = self.telemetry.clock if self.telemetry is not None else None
        t0 = clock() if clock else 0.0
        out = self._dispatch_guarded(
            TAG_TOKEN_GENERATION,
            lambda: self.app.forward(
                ids, pos, last_token_index=last, sampling_params=sampling,
                submodel=TAG_TOKEN_GENERATION, keep_batch_padding=True, **batch,
            ),
        )
        for key in ("tokens", "moe_held_pairs", "sparse_blocks_read", "sparse_blocks_live"):
            if key in out:  # the copies start now and ride behind the program
                out[key].copy_to_host_async()
        if prev is not None and self._chained_steps is not None:
            self._chained_steps.inc()
        for _, r in rows:
            r.pending += 1
        return _InFlight(
            rows=[(slot, r, r.preemptions) for slot, r in rows],
            outputs=out, t0=t0, record=fl.current if fl is not None else None,
            ends_a_row=any(r.remaining <= 0 for _, r in rows),
        )

    def _collect_decode(
        self, flight: _InFlight, finished: List[RequestOutput]
    ) -> None:
        """Collect half: wait for the flight's tokens, emit them, retire what
        finished. A row whose request left its slot since the dispatch (it
        finished on an EOS the host could not foresee, or was requeued) has
        its token dropped: never emitted, never counted. Its KV write was
        harmless: it landed in a block the request still owned at dispatch,
        at the position after its last token, which no reader attends to (a
        later owner of the block, or a follower that forks the cached chain,
        reads a position only after writing it itself, and the prefix cache
        holds the request's committed positions only); and the device runs
        programs in order, so a block freed on the host at the finish and
        handed to a later dispatch cannot be written under that dispatch. A
        recurrent-state slot an overrun step advanced is rewritten by the
        next admission's context-encoding pass, which computes the state from
        its inputs alone (the families' ``is_decode=False`` branch)."""
        phase = self._phase
        out = flight.outputs
        kept = self.flight is not None or self.telemetry is not None
        with phase("fetch"):
            toks = self._tokens_of(out)
            held = out.get("moe_held_pairs") if kept else None
            if held is not None:
                if self._moe_routed_layers is None:  # the same number every step
                    self._moe_routed_layers = int(out["moe_routed_layers"])
                held = int(held)
            blocks = None
            if kept and "sparse_blocks_read" in out:
                blocks = int(out["sparse_blocks_read"]), int(out["sparse_blocks_live"])
        if held is not None:
            self._note_moe_held_pairs(held, self._moe_routed_layers, flight.record)
        if blocks is not None:
            self._note_sparse_blocks(*blocks, flight.record)
        clock = self.telemetry.clock if self.telemetry is not None else None
        dt = None
        if clock:
            # a token's time: since its dispatch, or since the collect before
            # it where that came later (the chain's period)
            now = clock()
            dt = now - max(flight.t0, self._t_collected)
            self._t_collected = now
        with phase("emit"):
            emitted = 0
            for (slot, req, epoch), tok in zip(flight.rows, toks):
                if req.state != RUNNING or req.preemptions != epoch:
                    continue
                req.pending -= 1
                if req.span is not None:
                    req.span.tokens(1, dt)
                req.emit(int(tok))
                emitted += 1
                reason = req.check_finish()
                if reason:
                    self._finish(req, reason, finished)
            overrun = len(flight.rows) - emitted
            if overrun and self._overrun_tokens is not None:
                self._overrun_tokens.inc(overrun)
            if self.flight is not None:
                self.flight.note_decode_tokens(emitted, overrun, flight.record)

    def _decode_multistep(
        self,
        rows: List[Tuple[int, Request]],
        steps: int,
        finished: List[RequestOutput],
    ) -> None:
        phase = self._phase
        with phase("pack"):
            B = len(rows)
            eos = np.full((B, MULTISTEP_EOS_SLOTS), -1, dtype=np.int32)
            for i, (_, r) in enumerate(rows):
                for j, e in enumerate(r.params.eos_token_ids):
                    eos[i, j] = e
            batch = {
                "input_ids": np.array(
                    [[r.generated[-1]] for _, r in rows], dtype=np.int32
                ),
                "position_ids": np.array(
                    [[r.total_len - 1] for _, r in rows], dtype=np.int32
                ),
                "last_token_index": np.zeros((B,), dtype=np.int32),
                "sampling_params": SamplingParams.rows_tensor(
                    [r.params for _, r in rows]
                ),
                "eos_token_ids": eos,
                "pad_token_id": np.zeros((B,), dtype=np.int32),
                # per-row remaining budgets: the in-scan mask freezes a row
                # after its budget-hit token, which is what lets _choose_steps
                # hand near-EOS rows a window bigger than their budget
                "budget_steps": np.array(
                    [r.remaining for _, r in rows], dtype=np.int32
                ),
                "decode_steps": steps,
            }
            batch.update(self._layout_kwargs(rows))
            self._maybe_rng(batch)
            if self.flight is not None:
                self.flight.record_decode(
                    TAG_TOKEN_GENERATION_MULTISTEP, steps, rows,
                    self.tpu_config.tkg_batch_size,
                )
        clock = self.telemetry.clock if self.telemetry is not None else None
        t0 = clock() if clock else 0.0
        out = self._dispatch_guarded(
            "token_gen_multistep", lambda: self.app.token_gen_multistep(batch)
        )
        with phase("fetch"):
            toks = np.asarray(jax.device_get(out["tokens"]))[:B]  # (B, steps)
        dt = (clock() - t0) if clock else None
        with phase("emit"):
            total_emitted = 0
            for i, (slot, req) in enumerate(rows):
                emitted = 0
                for j in range(steps):
                    req.emit(int(toks[i, j]))
                    emitted += 1
                    reason = req.check_finish()
                    if reason:
                        # later in-window tokens for this row are pad-masked by
                        # the in-scan EOS/budget logic; discard them
                        self._finish(req, reason, finished)
                        break
                total_emitted += emitted
                if req.span is not None and emitted:
                    req.span.tokens(emitted, dt if dt is None else dt * emitted / steps)
            if self.flight is not None:
                self.flight.note_decode_tokens(total_emitted)

    def _use_device_loop(self, rows: List[Tuple[int, Request]]) -> bool:
        """Device-loop admissibility for THIS window: the submodel is
        compiled, every row's EOS list fits the baked (B, 8) slots, and at
        least one row has more than a single token left — a 1-token tail
        is the plain TKG program's home turf, a while-loop launch for it
        buys nothing."""
        if not self.device_loop:
            return False
        if any(
            len(r.params.eos_token_ids) > MULTISTEP_EOS_SLOTS for _, r in rows
        ):
            return False
        return max(r.remaining for _, r in rows) > 1

    def _decode_device_loop(
        self, rows: List[Tuple[int, Request]], finished: List[RequestOutput]
    ) -> None:
        """ONE ``tkg_device_loop`` launch serves every row to EOS / budget /
        fence: the while-loop body runs sample->embed->layers->KV-commit
        each iteration and the cond exits when all rows halt, so a batch
        with heterogeneous remaining budgets costs a single dispatch
        instead of one per token (or per rung). ``device_loop_fence`` caps
        tokens per launch — the preemption fence: admission, retirement,
        and preemption all get a scheduling point between launches."""
        phase = self._phase
        with phase("pack"):
            tc = self.tpu_config
            B = len(rows)
            eos = np.full((B, MULTISTEP_EOS_SLOTS), -1, dtype=np.int32)
            for i, (_, r) in enumerate(rows):
                for j, e in enumerate(r.params.eos_token_ids):
                    eos[i, j] = e
            budgets = np.array([r.remaining for _, r in rows], dtype=np.int32)
            fence = int(getattr(tc, "device_loop_fence", 0) or 0)
            if fence:
                budgets = np.minimum(budgets, fence)
            cap = self._dloop.select_cap(int(budgets.max()))
            batch = {
                "input_ids": np.array(
                    [[r.generated[-1]] for _, r in rows], dtype=np.int32
                ),
                "position_ids": np.array(
                    [[r.total_len - 1] for _, r in rows], dtype=np.int32
                ),
                "last_token_index": np.zeros((B,), dtype=np.int32),
                "sampling_params": SamplingParams.rows_tensor(
                    [r.params for _, r in rows]
                ),
                "eos_token_ids": eos,
                "pad_token_id": np.zeros((B,), dtype=np.int32),
                "budget_steps": budgets,
                "loop_cap": cap,
            }
            batch.update(self._layout_kwargs(rows))
            if self._dloop.needs_rng:
                batch["rng"] = self._rng.next()
        clock = self.telemetry.clock if self.telemetry is not None else None
        t0 = clock() if clock else 0.0
        out = self._dispatch_guarded(
            "token_gen_device_loop", lambda: self.app.token_gen_device_loop(batch)
        )
        with phase("fetch"):
            toks = np.asarray(jax.device_get(out["tokens"]))[:B]  # (B, cap)
            iters = int(jax.device_get(out["loop_iters"]))
        dt = (clock() - t0) if clock else None
        if self._dloop.needs_rng and iters > 1:
            # iteration t sampled with counter base+t IN-GRAPH; land the
            # host schedule where ``iters`` chained 1-step dispatches would
            # have (the sampled loop-ON/OFF parity contract)
            self._rng.advance(iters - 1)
        with phase("emit"):
            total_emitted = 0
            for i, (slot, req) in enumerate(rows):
                emitted = 0
                for j in range(min(iters, int(budgets[i]))):
                    req.emit(int(toks[i, j]))
                    emitted += 1
                    reason = req.check_finish()
                    if reason:
                        # this row halted mid-loop; its later buffer columns
                        # are pad fill — discard them
                        self._finish(req, reason, finished)
                        break
                total_emitted += emitted
                if req.span is not None and emitted:
                    req.span.tokens(
                        emitted, dt if dt is None else dt * emitted / max(iters, 1)
                    )
            if self.flight is not None:
                self.flight.record_decode(
                    TAG_DEVICE_LOOP, cap, rows, tc.tkg_batch_size,
                    tokens_emitted=total_emitted,
                )
            if self._loop_launches is not None:
                lbl = str(cap)
                self._loop_launches.inc(cap=lbl)
                self._loop_iters_total.inc(iters, cap=lbl)
                self._loop_tokens_total.inc(total_emitted, cap=lbl)
                self._loop_tokens_per_dispatch.set(float(total_emitted))

    # -- retirement ---------------------------------------------------------
    def _finish(
        self, req: Request, reason: str, finished: List[RequestOutput]
    ) -> None:
        slot = req.slot  # retire() recycles it; the record keeps the row
        self.scheduler.retire(req, reason)
        metrics: Dict[str, float] = {"preemptions": req.preemptions}
        if req.recoveries:
            metrics["recoveries"] = req.recoveries
        if req.fork_parent_id is not None:
            # n>1 sibling: callers group continuations by the parent id
            metrics["parent_request_id"] = req.fork_parent_id
        if req.span is not None:
            req.span.finish()
            metrics["ttft_s"] = req.span.ttft_s
            metrics["e2e_s"] = req.span.t_end - req.span.t_start
            n_dec = max(len(req.generated) - 1, 0)
            if n_dec and req.span.ttft_s is not None:
                metrics["tpot_s"] = (
                    metrics["e2e_s"] - req.span.ttft_s
                ) / n_dec
        if self.flight is not None:
            self.flight.record_retirement(req.request_id, slot, reason)
        if self.slo is not None and req.span is not None and reason != "error":
            # error finishes never count toward SLO attainment — the same
            # exclusion goodput_summary applies to served throughput
            kinds = self.slo.observe(
                metrics.get("ttft_s"),
                metrics.get("tpot_s"),
                tokens_out=len(req.generated),
                t_finish=req.span.t_end,
            )
            metrics["slo_breaches"] = kinds
            if kinds and self.flight is not None:
                # deferred to step()'s end: the bundle must include the
                # StepRecord of the very step this finish happened in
                self._pending_breaches.append((req, kinds))
        if self.qos is not None and reason != "error":
            # per-class attainment rides the same ttft/tpot the span
            # measured (and the same error exclusion as the engine SLO)
            self.qos.observe_finish(
                req, metrics.get("ttft_s"), metrics.get("tpot_s")
            )
        if (
            self.sentinel is not None
            and reason != "error"
            and self.sentinel.should_replay(req)
        ):
            # shadow replay: teacher-force the retired request through the
            # offline toolkit's logit probe and token-match what was
            # actually streamed; divergence -> mismatch counter + numerics
            # bundle with the index and tol-map summary
            self.sentinel.verify_replay(req, "shadow")
        finished.append(
            RequestOutput(
                request_id=req.request_id,
                prompt=list(req.prompt),
                token_ids=list(req.generated),
                finish_reason=reason,
                metrics=metrics,
                error=req.error,
            )
        )

    # -- helpers ------------------------------------------------------------
    def scheduler_state(self) -> dict:
        """JSON-able scheduler picture for postmortem bundles and probes:
        the FCFS queue, each slot's occupant, and the KV headroom."""
        sch = self.scheduler
        return {
            "waiting": [
                {
                    "request_id": r.request_id,
                    "state": r.state,
                    "preemptions": r.preemptions,
                    "prompt_tokens": len(r.prompt),
                    "generated": len(r.generated),
                }
                for r in sch.waiting
            ],
            "slots": [
                None if r is None else {
                    "request_id": r.request_id,
                    "state": r.state,
                    "prefilled": r.num_prefilled,
                    "prefill_target": r.prefill_target,
                    "generated": len(r.generated),
                    "remaining": r.remaining,
                }
                for r in sch.slots
            ],
            "kv_blocks_free": (
                self.block_manager.num_free_blocks()
                if self.block_manager is not None else None
            ),
            "watermark_blocks": sch.config.watermark_blocks,
            "prefix_cache": (
                None if self.prefix_cache is None else {
                    "cached_blocks": len(self.prefix_cache),
                    "reclaimable": self.prefix_cache.reclaimable(),
                    "hits": self.prefix_cache.hits_n,
                    "misses": self.prefix_cache.misses_n,
                    "evictions": self.prefix_cache.evictions_n,
                    "cow_copies": self.prefix_cache.cow_copies_n,
                    "tokens_saved": self.prefix_cache.tokens_saved_n,
                }
            ),
        }

    def _note_moe_held_pairs(self, pairs: int, routed_layers: int, record) -> None:
        """A share of an expert-parallel model counts, inside its
        token-generation program, the (row, expert) pairs routed to the
        experts it holds (models/base.py causal_lm_forward). ``record``: the
        step that dispatched the program (the count arrives with its
        collect, which may be a step later)."""
        if self.flight is not None:
            self.flight.note_moe_held_pairs(pairs, routed_layers, record)
        if self.telemetry is None:
            return
        if self._moe_held_pairs is None:
            r = self.telemetry.registry
            self._moe_held_pairs = r.counter(
                "nxdi_moe_held_pairs_total",
                "(row, expert) pairs of token generation routed to held experts, "
                "summed over the routed layers (batch-padding rows included)",
            )
            self._moe_routed_layer_steps = r.counter(
                "nxdi_moe_routed_layers_steps_total",
                "routed layers x token-generation steps behind nxdi_moe_held_pairs_total",
            )
        self._moe_held_pairs.inc(pairs)
        self._moe_routed_layer_steps.inc(routed_layers)

    def _note_sparse_blocks(self, read: int, live: int, record) -> None:
        """A model with block-sparse attention layers counts, inside its
        token-generation program, the pool blocks it read and those visible to
        it (models/minicpm_sala), summed over rows, KV heads and sparse layers."""
        if self.flight is not None:
            self.flight.note_sparse_blocks(read, live, record)
        if self.telemetry is None:
            return
        if self._sparse_blocks is None:
            r = self.telemetry.registry
            self._sparse_blocks = (
                r.counter(
                    "nxdi_sparse_blocks_read_total",
                    "pool blocks token generation read in its block-sparse layers "
                    "(rows x KV heads x layers; batch-padding rows included)",
                ),
                r.counter(
                    "nxdi_sparse_blocks_live_total",
                    "pool blocks visible to those reads: what a dense read would take",
                ),
            )
        self._sparse_blocks[0].inc(read)
        self._sparse_blocks[1].inc(live)

    def _note_prefill_moe(self, pairs: int, expert_rows: int) -> None:
        """A share's prefill program whose expert layers took the compact form
        (ops/moe.py) counts the live pairs of its routed layers (a held expert
        and a real row: bucket padding has no slot) and the slot rows its
        expert matmuls multiplied for them; they land in the open step's
        record (the prefill is collected in the step that ran it)."""
        if self.flight is not None:
            self.flight.note_prefill_moe(pairs, expert_rows)
        if self.telemetry is None:
            return
        if self._prefill_moe_held_pairs is None:
            r = self.telemetry.registry
            self._prefill_moe_held_pairs = r.counter(
                "nxdi_moe_prefill_held_pairs_total",
                "(row, expert) pairs of compact-form prefills routed to held experts, "
                "summed over the routed layers (bucket-padding rows left out)",
            )
            self._prefill_moe_expert_rows = r.counter(
                "nxdi_moe_prefill_expert_rows_total",
                "slot rows the expert matmuls of compact-form prefills multiplied "
                "(held experts x slots an expert x trips, summed over the routed layers)",
            )
        self._prefill_moe_held_pairs.inc(pairs)
        self._prefill_moe_expert_rows.inc(expert_rows)

    def _tokens_of(self, outputs) -> np.ndarray:
        # shared with the HF adapter (ops/sampling.py): ONE extraction rule,
        # ONE rng schedule — the greedy-parity anchor depends on it
        return extract_next_tokens(outputs)

    def preempt_youngest(self) -> Optional[Request]:
        """Force one recompute-style preemption (tests / demos)."""
        return self.scheduler.preempt_youngest()
