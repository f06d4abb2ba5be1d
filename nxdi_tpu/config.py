"""Configuration system for the TPU-native inference framework.

Two-level design mirroring the reference framework's contract
(reference: models/config.py:84 ``NeuronConfig``, :813 ``InferenceConfig``):

- :class:`TpuConfig` — runtime/feature flags (parallel degrees, bucketing,
  sampling, speculation, quantization, ...). Everything the compiler/runtime
  needs that is NOT a model hyperparameter.
- :class:`InferenceConfig` — model hyperparameters, typically adapted from a
  HuggingFace ``config.json``, plus a ``tpu_config`` attribute. Serialized to
  JSON next to compiled artifacts so compile-time and run-time agree
  (reference: models/config.py:891-1002).

The JSON artifact is intentionally shaped like the reference's
``neuron_config.json`` so tooling that reads it keeps working.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

import jax.numpy as jnp
import numpy as np

CONFIG_FILE = "tpu_config.json"

_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
    "float8_e4m3": jnp.float8_e4m3fn,
    "float8_e5m2": jnp.float8_e5m2,
    "int8": jnp.int8,
}


def to_jax_dtype(dtype) -> Any:
    """Map a string (or jnp dtype) to a jnp dtype (reference: utils/distributed.py analog)."""
    if isinstance(dtype, str):
        key = dtype.replace("torch.", "")
        if key not in _DTYPES:
            raise ValueError(f"Unsupported dtype {dtype!r}; options: {sorted(_DTYPES)}")
        return _DTYPES[key]
    return dtype


def dtype_name(dtype) -> str:
    for name, dt in _DTYPES.items():
        if dt == dtype:
            return name
    return str(dtype)


class OnDeviceSamplingConfig:
    """Sampling-on-device flags (reference: models/config.py:1028)."""

    def __init__(self, **kwargs):
        self.do_sample = kwargs.pop("do_sample", False)
        self.top_k = kwargs.pop("top_k", 1)
        self.top_p = kwargs.pop("top_p", 1.0)
        self.temperature = kwargs.pop("temperature", 1.0)
        self.dynamic = kwargs.pop("dynamic", True)  # per-request sampling params tensor
        self.global_topk = kwargs.pop("global_topk", 256)  # stage-1 shard top-k width
        self.deterministic = kwargs.pop("deterministic", False)
        self.on_device_sampling_seed = kwargs.pop("on_device_sampling_seed", 0)
        # batch-sharded sampling over the tp world (reference:
        # DataParallelSampler, modules/generation/sampling.py:469-569): each
        # shard runs the top-k stages on its batch rows, GSPMD gathers tokens
        self.dp_sampling = kwargs.pop("dp_sampling", False)
        if kwargs:
            raise ValueError(f"Unknown OnDeviceSamplingConfig args: {sorted(kwargs)}")

    def to_dict(self):
        return dict(self.__dict__)


class KVQuantizationConfig:
    """KV-cache quantization (reference: models/config.py:300-306, kv_cache_manager.py:642)."""

    def __init__(self, **kwargs):
        self.dtype = kwargs.pop("dtype", "float8_e4m3")
        # direct_cast | per_tensor | per_key | per_channel
        # (reference: QuantizationType PER_TENSOR/PER_KEY/PER_CHANNEL
        # _SYMMETRIC scale buffers, kv_cache_manager.py:642-692)
        self.scale_mode = kwargs.pop("scale_mode", "direct_cast")
        # per_tensor: values are stored as value/scale in fp8 and rescaled on
        # read. Static scales, typically from offline amax calibration.
        self.k_scale = float(kwargs.pop("k_scale", 1.0))
        self.v_scale = float(kwargs.pop("v_scale", 1.0))
        # per_key: per-layer, per-kv-head scales, shape (L, KV).
        # per_channel: per-layer, per-head-dim-channel scales, shape (L, D).
        # Accepted as nested lists/arrays, or loaded from ``scales_path`` (an
        # .npz with k_scales/v_scales produced by
        # kvcache.calibration.calibrate_kv_scales).
        self.scales_path = kwargs.pop("scales_path", None)
        k_scales = kwargs.pop("k_scales", None)
        v_scales = kwargs.pop("v_scales", None)
        if self.scales_path is not None and k_scales is None:
            with np.load(self.scales_path) as z:
                k_scales = z["k_scales"]
                v_scales = z["v_scales"]
        if k_scales is not None:
            k_scales = np.asarray(k_scales, dtype=np.float32)
            v_scales = np.asarray(v_scales, dtype=np.float32)
        self.k_scales = k_scales
        self.v_scales = v_scales
        if k_scales is not None and self.scale_mode == "per_tensor":
            # calibration's per_tensor mode returns (L,) per-layer arrays;
            # the per-tensor layout takes one static scalar — collapse to
            # the max so the documented calibrate->config flow works
            self.k_scale = float(np.max(k_scales))
            self.v_scale = float(np.max(v_scales))
            self.k_scales = self.v_scales = None
        elif k_scales is not None and self.scale_mode not in ("per_key", "per_channel"):
            raise ValueError(
                "k_scales/v_scales arrays are only consumed by "
                "scale_mode='per_tensor'|'per_key'|'per_channel'; got "
                f"scale_mode={self.scale_mode!r}"
            )
        if self.scale_mode not in ("direct_cast", "per_tensor", "per_key", "per_channel"):
            raise ValueError(
                "kv quant scale_mode must be direct_cast|per_tensor|per_key|"
                f"per_channel, got {self.scale_mode!r}"
            )
        if self.scale_mode == "direct_cast" and (self.k_scale != 1.0 or self.v_scale != 1.0):
            raise ValueError("k_scale/v_scale require scale_mode='per_tensor'")
        if self.scale_mode in ("per_key", "per_channel") and self.k_scales is None:
            raise ValueError(
                f"scale_mode={self.scale_mode!r} needs k_scales/v_scales arrays "
                "(or scales_path) from calibration "
                "(nxdi_tpu.kvcache.calibration.calibrate_kv_scales)"
            )
        if kwargs:
            raise ValueError(f"Unknown KVQuantizationConfig args: {sorted(kwargs)}")

    def to_dict(self):
        d = dict(self.__dict__)
        for key in ("k_scales", "v_scales"):
            if d.get(key) is not None:
                d[key] = np.asarray(d[key]).tolist()
        return d


class ChunkedPrefillConfig:
    """Chunked prefill over block KV (reference: models/config.py:1042)."""

    def __init__(self, **kwargs):
        self.max_num_seqs = kwargs.pop("max_num_seqs", 8)
        self.chunk_size = kwargs.pop("chunk_size", 512)
        self.kernel_q_tile_size = kwargs.pop("kernel_q_tile_size", 128)
        self.kernel_kv_tile_size = kwargs.pop("kernel_kv_tile_size", 512)
        if kwargs:
            raise ValueError(f"Unknown ChunkedPrefillConfig args: {sorted(kwargs)}")

    def to_dict(self):
        return dict(self.__dict__)


class TelemetryConfig:
    """Serving telemetry (nxdi_tpu/telemetry): always-on metrics registry +
    per-request lifecycle spans owned by the application (``app.telemetry``).

    ``detail``:
      - ``"off"``   — nothing records.
      - ``"basic"`` (default) — all metrics/spans record; dispatch latency is
        the host cost only (never forces a device sync).
      - ``"full"``  — host-path dispatches additionally block until outputs
        are ready before recording, so latency histograms measure true step
        time (``SubmodelProfiler`` flips this on while attached).

    ``max_spans`` bounds the request-span ring buffer (Perfetto export).

    Flight recorder (nxdi_tpu/telemetry/flight.py; serving engine only):

    ``flight`` enables the per-step engine flight recorder;
    ``flight_records`` bounds its StepRecord ring buffer;
    ``postmortem_dir`` — directory where trigger-fired postmortem bundles
    (SLO breach, preemption storm, retrace-guard trip) are written as JSON;
    ``None`` keeps the recorder in-memory only (manual dumps still work).
    ``storm_window`` / ``storm_preemptions`` — a preemption storm fires the
    postmortem trigger when the last ``storm_window`` engine steps carried
    >= ``storm_preemptions`` recompute preemptions.

    ``replica_id`` — stable replica identity for the fleet observatory
    (telemetry/fleet.py): rides every JSON snapshot as
    ``_process.replica_id`` and becomes the ``replica`` label on federated
    series. None = ``"<hostname>:<pid>"``, derived once per process.

    Distributed tracing (nxdi_tpu/telemetry/tracing.py):

    ``trace`` enables per-hop trace recording (ingest queueing, prefill,
    handoff export/import, first decode token) into a bounded per-replica
    buffer served at ``/traces``; a no-op at ``detail="off"`` like every
    other surface. ``trace_buffer`` bounds retained hop spans (overflow
    counts ``nxdi_traces_dropped_total``); ``trace_sample_rate`` is the
    deterministic credit-accumulator rate applied when THIS process mints
    a fresh context (requests arriving with a valid ``traceparent`` keep
    the sender's sampling decision).
    """

    def __init__(self, **kwargs):
        self.enabled = bool(kwargs.pop("enabled", True))
        self.detail = kwargs.pop("detail", "basic")
        self.max_spans = int(kwargs.pop("max_spans", 256))
        self.trace = bool(kwargs.pop("trace", True))
        self.trace_buffer = int(kwargs.pop("trace_buffer", 256))
        self.trace_sample_rate = float(kwargs.pop("trace_sample_rate", 1.0))
        # stable replica identity (fleet observatory, telemetry/fleet.py):
        # the label every federated series carries for this process. None =
        # derived once per Telemetry as "<hostname>:<pid>" — stable for the
        # process lifetime; pin it here for stable labels across restarts.
        rid = kwargs.pop("replica_id", None)
        self.replica_id = None if rid is None else str(rid)
        self.flight = bool(kwargs.pop("flight", True))
        self.flight_records = int(kwargs.pop("flight_records", 512))
        self.postmortem_dir = kwargs.pop("postmortem_dir", None)
        self.storm_window = int(kwargs.pop("storm_window", 32))
        self.storm_preemptions = int(kwargs.pop("storm_preemptions", 8))
        if self.detail not in ("off", "basic", "full"):
            raise ValueError(
                f"telemetry detail must be 'off'|'basic'|'full', got {self.detail!r}"
            )
        if self.max_spans < 1:
            raise ValueError("telemetry max_spans must be >= 1")
        if self.trace_buffer < 1:
            raise ValueError("telemetry trace_buffer must be >= 1")
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError(
                "telemetry trace_sample_rate must be within [0, 1]"
            )
        if self.flight_records < 1:
            raise ValueError("telemetry flight_records must be >= 1")
        if self.storm_window < 1 or self.storm_preemptions < 1:
            raise ValueError(
                "telemetry storm_window and storm_preemptions must be >= 1"
            )
        if kwargs:
            raise ValueError(f"Unknown TelemetryConfig args: {sorted(kwargs)}")

    def to_dict(self):
        return dict(self.__dict__)


class SloConfig:
    """Declared serving SLOs (nxdi_tpu/telemetry/slo.py): latency targets the
    SLO tracker measures per-request attainment against.

    ``ttft_s`` — time-to-first-token target in seconds (None = not declared);
    ``tpot_s`` — mean inter-token (time-per-output-token) target in seconds.
    A request ATTAINS its SLO when every declared target holds with
    ``value <= target`` (exactly at the target is attained; the breach is
    strict ``>``). ``window`` bounds the rolling population behind the
    ``nxdi_slo_attainment_pct`` / ``nxdi_slo_goodput_tok_s`` gauges.
    """

    def __init__(self, **kwargs):
        ttft = kwargs.pop("ttft_s", None)
        tpot = kwargs.pop("tpot_s", None)
        self.ttft_s = None if ttft is None else float(ttft)
        self.tpot_s = None if tpot is None else float(tpot)
        self.window = int(kwargs.pop("window", 256))
        if kwargs:
            raise ValueError(f"Unknown SloConfig args: {sorted(kwargs)}")
        if self.ttft_s is None and self.tpot_s is None:
            raise ValueError("SloConfig needs at least one of ttft_s / tpot_s")
        if (self.ttft_s is not None and self.ttft_s <= 0) or (
            self.tpot_s is not None and self.tpot_s <= 0
        ):
            raise ValueError("SLO targets must be positive seconds")
        if self.window < 1:
            raise ValueError("SLO window must be >= 1")

    def to_dict(self):
        return dict(self.__dict__)


class QosConfig:
    """QoS control plane, engine tier (nxdi_tpu/control/qos.py): multi-tenant
    token-bucket quotas + deadline-aware admission/preemption over the
    priority classes ``interactive`` | ``batch`` | ``best_effort``.

    ``default_class`` — priority class of requests that declare none;
    ``class_slos`` — per-class latency targets (class name -> SloConfig /
    its kwargs dict / None = no deadline for that class). Classes absent
    from the map fall back to the built-in defaults; an explicit None
    entry disables the class's deadline. Slack against these targets is
    what deadline-aware admission orders the waiting queue by
    (``deadline = arrival + ttft_s + tpot_s * |generated|``);
    ``quotas`` — per-tenant token buckets (tenant -> {"refill_per_s",
    "burst"}); a submission is charged ``prompt + max_new_tokens`` at
    admission and rejected with a deterministic 429-style error finish
    when its tenant's bucket cannot cover it;
    ``default_quota`` — bucket for tenants not in ``quotas`` (None =
    unbounded — the greedy-parity default);
    ``default_tenant`` — tenant identity of requests that declare none;
    ``deadline_admission`` / ``deadline_preemption`` — enable the two
    scheduler hooks independently;
    ``slack_guard_s`` — a RUNNING request whose slack is below this is
    never chosen as a preemption victim (it is about to breach; evicting
    it guarantees the breach) unless every candidate is below the guard;
    ``window`` — rolling per-class attainment population behind the
    ``nxdi_qos_slo_attainment_pct{class}`` gauges.
    """

    #: built-in per-class deadline targets (seconds); best_effort has none
    DEFAULT_CLASS_SLOS = {
        "interactive": {"ttft_s": 0.5, "tpot_s": 0.1},
        "batch": {"ttft_s": 5.0, "tpot_s": 0.5},
        "best_effort": None,
    }

    def __init__(self, **kwargs):
        from nxdi_tpu.ops.sampling import PRIORITY_CLASSES

        self.default_class = str(kwargs.pop("default_class", "batch"))
        if self.default_class not in PRIORITY_CLASSES:
            raise ValueError(
                f"qos default_class must be one of {PRIORITY_CLASSES}, "
                f"got {self.default_class!r}"
            )
        slos = dict(kwargs.pop("class_slos", None) or {})
        unknown = sorted(set(slos) - set(PRIORITY_CLASSES))
        if unknown:
            raise ValueError(f"qos class_slos has unknown classes: {unknown}")
        self.class_slos = {}
        for cls in PRIORITY_CLASSES:
            slo = slos.get(cls, self.DEFAULT_CLASS_SLOS[cls])
            if isinstance(slo, dict):
                slo = SloConfig(**slo)
            if slo is not None and not isinstance(slo, SloConfig):
                raise ValueError(
                    f"qos class_slos[{cls!r}] must be an SloConfig, a dict "
                    f"of its kwargs, or None — got {type(slo)}"
                )
            self.class_slos[cls] = slo
        self.default_tenant = str(kwargs.pop("default_tenant", "default"))
        self.quotas = {
            str(t): self._quota(t, q)
            for t, q in dict(kwargs.pop("quotas", None) or {}).items()
        }
        dq = kwargs.pop("default_quota", None)
        self.default_quota = None if dq is None else self._quota("*", dq)
        self.deadline_admission = bool(kwargs.pop("deadline_admission", True))
        self.deadline_preemption = bool(kwargs.pop("deadline_preemption", True))
        self.slack_guard_s = float(kwargs.pop("slack_guard_s", 0.05))
        self.window = int(kwargs.pop("window", 256))
        if kwargs:
            raise ValueError(f"Unknown QosConfig args: {sorted(kwargs)}")
        if self.slack_guard_s < 0:
            raise ValueError("qos slack_guard_s must be >= 0")
        if self.window < 1:
            raise ValueError("qos window must be >= 1")

    @staticmethod
    def _quota(tenant, q) -> dict:
        q = dict(q)
        try:
            refill = float(q.pop("refill_per_s"))
            burst = float(q.pop("burst"))
        except KeyError as e:
            raise ValueError(
                f"qos quota for tenant {tenant!r} needs refill_per_s and "
                f"burst, missing {e}"
            )
        if q:
            raise ValueError(
                f"Unknown qos quota keys for tenant {tenant!r}: {sorted(q)}"
            )
        if refill < 0 or burst <= 0:
            raise ValueError(
                f"qos quota for tenant {tenant!r} needs refill_per_s >= 0 "
                "and burst > 0"
            )
        return {"refill_per_s": refill, "burst": burst}

    def to_dict(self):
        d = dict(self.__dict__)
        d["class_slos"] = {
            c: None if s is None else s.to_dict()
            for c, s in self.class_slos.items()
        }
        return d


class AutoscaleConfig:
    """QoS control plane, fleet tier (nxdi_tpu/control/autoscaler.py): the
    policy loop that closes FleetMonitor load signals back into replica
    lifecycle.

    ``interval_s`` — loop pace of the background autoscaler thread;
    ``ewma_alpha`` — smoothing weight of the fleet-mean load-score trend
    (``trend = alpha * mean + (1 - alpha) * trend``; 1.0 = unsmoothed);
    ``scale_up_score`` / ``scale_down_score`` — hysteresis band on the
    smoothed trend: above the high watermark the fleet grows, below the
    low one it shrinks, in between it holds (the band is what stops
    flapping on a noisy signal);
    ``min_replicas`` / ``max_replicas`` — hard bounds on ACTIVE (non-
    draining) replicas;
    ``cooldown_s`` — minimum seconds between two scaling actions (retire
    of an already-drained replica is exempt — it frees resources and
    cannot flap);
    ``rebalance_ratio`` — prefill:decode mean-score ratio beyond which the
    role mix rebalances one replica toward the pressured role (applies
    symmetrically as ratio and 1/ratio; 0 disables role rebalance);
    ``decision_ring`` — bound on the journaled decision trace behind the
    ``/autoscale`` endpoint and ``cli.fleet --autoscale-log``.
    """

    def __init__(self, **kwargs):
        self.interval_s = float(kwargs.pop("interval_s", 1.0))
        self.ewma_alpha = float(kwargs.pop("ewma_alpha", 0.5))
        self.scale_up_score = float(kwargs.pop("scale_up_score", 6.0))
        self.scale_down_score = float(kwargs.pop("scale_down_score", 1.5))
        self.min_replicas = int(kwargs.pop("min_replicas", 1))
        self.max_replicas = int(kwargs.pop("max_replicas", 8))
        self.cooldown_s = float(kwargs.pop("cooldown_s", 10.0))
        self.rebalance_ratio = float(kwargs.pop("rebalance_ratio", 0.0))
        self.decision_ring = int(kwargs.pop("decision_ring", 256))
        if kwargs:
            raise ValueError(f"Unknown AutoscaleConfig args: {sorted(kwargs)}")
        if self.interval_s <= 0:
            raise ValueError("autoscale interval_s must be > 0")
        if not 0.0 < self.ewma_alpha <= 1.0:
            raise ValueError("autoscale ewma_alpha must be in (0, 1]")
        if self.scale_down_score >= self.scale_up_score:
            raise ValueError(
                "autoscale needs scale_down_score < scale_up_score "
                "(the hysteresis band)"
            )
        if self.min_replicas < 1 or self.max_replicas < self.min_replicas:
            raise ValueError(
                "autoscale needs 1 <= min_replicas <= max_replicas"
            )
        if self.cooldown_s < 0:
            raise ValueError("autoscale cooldown_s must be >= 0")
        if self.rebalance_ratio < 0:
            raise ValueError("autoscale rebalance_ratio must be >= 0 (0 off)")
        if self.decision_ring < 1:
            raise ValueError("autoscale decision_ring must be >= 1")

    def to_dict(self):
        return dict(self.__dict__)


class SentinelConfig:
    """Numerics sentinel (nxdi_tpu/telemetry/sentinel.py): online correctness
    observability for the serving path — in-graph logit-health stats,
    sampled shadow-replay verification, and the preemption-replay invariant.

    ``logit_health`` — compile a small in-graph reduction over each
    dispatch's sampled-position logit row block (NaN/Inf counts, max|logit|,
    mean entropy, top1-top2 margin) exported as ``nxdi_numerics_*`` series
    per (submodel, bucket); a nonzero NaN/Inf count fires the ``numerics``
    postmortem trigger through the flight recorder.
    ``replay_rate`` — fraction of RETIRED greedy requests teacher-force
    replayed through the static all-position logit probe
    (utils/accuracy.py) and token-matched against what the engine actually
    streamed (0.0 = off, 1.0 = every request; deterministic credit
    accumulator, not a random draw, so tests and fleets are reproducible).
    ``preemption_check`` — on every recompute-resume, verify the replayed
    ``prompt + generated`` prefix reproduces the pre-preemption tokens
    exactly (greedy rows) — a mismatch counts
    ``nxdi_sentinel_replay_mismatch_total{kind="preemption"}`` and fires a
    ``numerics`` bundle instead of silently serving a forked continuation.
    ``divergence_tol`` / ``tol_map`` — tolerance (and per-index overrides,
    accuracy.py tol-map convention) on the replay's logit-margin report;
    token equality is always strict.
    ``bundle_cooldown`` — minimum dispatches between two ``numerics``
    bundles of the same kind (a persistent NaN must not write a bundle per
    step).
    """

    def __init__(self, **kwargs):
        self.logit_health = bool(kwargs.pop("logit_health", True))
        self.replay_rate = float(kwargs.pop("replay_rate", 0.0))
        self.preemption_check = bool(kwargs.pop("preemption_check", True))
        self.divergence_tol = float(kwargs.pop("divergence_tol", 0.001))
        tol_map = kwargs.pop("tol_map", None)
        # JSON round trips stringify int keys; accept both spellings
        self.tol_map = (
            None if tol_map is None
            else {int(k): float(v) for k, v in dict(tol_map).items()}
        )
        self.bundle_cooldown = int(kwargs.pop("bundle_cooldown", 64))
        if kwargs:
            raise ValueError(f"Unknown SentinelConfig args: {sorted(kwargs)}")
        if not 0.0 <= self.replay_rate <= 1.0:
            raise ValueError("sentinel replay_rate must be in [0, 1]")
        if self.divergence_tol < 0:
            raise ValueError("sentinel divergence_tol must be >= 0")
        if self.bundle_cooldown < 1:
            raise ValueError("sentinel bundle_cooldown must be >= 1")

    def to_dict(self):
        return dict(self.__dict__)


class FleetConfig:
    """Fleet observatory (nxdi_tpu/telemetry/fleet.py): how a
    :class:`~nxdi_tpu.telemetry.fleet.FleetMonitor` polls N replica
    ``/snapshot`` endpoints and classifies their health.

    ``poll_interval_s`` — seconds between poll rounds (``cli.fleet --watch``
    and the ``--serve`` federation endpoint pace on this);
    ``timeout_s`` — per-replica HTTP timeout (a poll can never hang the
    monitor longer than this per replica);
    ``staleness_s`` — a snapshot whose embedded ``_process.snapshot_unix_s``
    is older than this counts as a FAILED poll even when transport
    succeeded (a wedged replica keeps answering with frozen metrics — the
    age-out is what catches it);
    ``unreachable_failures`` — consecutive failed polls before a replica
    transitions DEGRADED -> UNREACHABLE (the first failure is DEGRADED
    unless this is 1); UNREACHABLE replicas leave the fleet aggregates;
    ``backoff_base_s`` / ``backoff_max_s`` — per-replica exponential
    backoff between polls of a FAILING replica
    (``min(base * 2**(failures-1), max)``); healthy replicas poll every
    round.
    """

    def __init__(self, **kwargs):
        self.poll_interval_s = float(kwargs.pop("poll_interval_s", 1.0))
        self.timeout_s = float(kwargs.pop("timeout_s", 2.0))
        self.staleness_s = float(kwargs.pop("staleness_s", 10.0))
        self.unreachable_failures = int(kwargs.pop("unreachable_failures", 3))
        self.backoff_base_s = float(kwargs.pop("backoff_base_s", 0.5))
        self.backoff_max_s = float(kwargs.pop("backoff_max_s", 30.0))
        if kwargs:
            raise ValueError(f"Unknown FleetConfig args: {sorted(kwargs)}")
        if self.poll_interval_s <= 0 or self.timeout_s <= 0:
            raise ValueError("fleet poll_interval_s and timeout_s must be > 0")
        if self.staleness_s <= 0:
            raise ValueError("fleet staleness_s must be > 0")
        if self.unreachable_failures < 1:
            raise ValueError("fleet unreachable_failures must be >= 1")
        if self.backoff_base_s <= 0 or self.backoff_max_s < self.backoff_base_s:
            raise ValueError(
                "fleet backoff needs 0 < backoff_base_s <= backoff_max_s"
            )

    def to_dict(self):
        return dict(self.__dict__)


class RouterConfig:
    """Replica router tier (nxdi_tpu/router): dispatch/failover/shedding
    knobs over the fleet observatory's load signals.

    ``degraded_penalty`` — score added to a DEGRADED replica when ranking a
    NEW dispatch (it stays dispatchable — its data is recent by the fleet
    age-out — but healthy peers win ties decisively); existing session pins
    survive DEGRADED so multi-turn traffic keeps its warm KV;
    ``inflight_weight`` — per-request weight of the router's OWN live
    assignment count in the ranking (least-outstanding-requests: polled
    load signals lag a poll interval, the local term keeps a burst between
    polls from landing wholesale on one replica; 0 ranks on the pinned
    fleet score alone);
    ``shed_queue_depth`` — router-level load-shedding watermark: a submit
    is rejected with explicit backpressure (HTTP 429, counted in
    ``nxdi_router_sheds_total``) when EVERY dispatchable replica's
    queue-depth gauge exceeds this;
    ``shed_class_factors`` — class-aware shedding (QoS control plane):
    per-priority-class multipliers on the shed watermark, so under
    pressure ``best_effort`` sheds first (factor < 1) while
    ``interactive`` keeps landing until the fleet is far deeper
    underwater (factor > 1). Requests without a priority class shed at
    the base watermark (factor 1.0);
    ``max_failovers`` — bounded retry: how many times one request may be
    re-dispatched after its replica fails (None = replica count - 1, i.e.
    every other replica gets one chance);
    ``stream_failures`` — consecutive transport failures polling one
    request's upstream stream before the router forces a health poll and
    takes the failover decision (1 = fail over on the first error);
    ``ingest_timeout_s`` — per-call HTTP timeout against replica ingest
    endpoints (/submit, /stream, /drain);
    ``poll_interval_s`` — background health/load poll cadence of the
    router's embedded FleetMonitor (``Router.start()``);
    ``max_sessions`` — LRU bound on the session-affinity pin table;
    ``max_requests`` — bound on retained finished-request records (live
    requests are never evicted);
    ``trace_sample_rate`` — deterministic credit-accumulator sampling rate
    for distributed traces minted at submit (telemetry/tracing.py): every
    submission carries a trace id regardless, but only sampled requests
    record hop spans (0 disables recording entirely);
    ``trace_buffer`` — bound on the router's retained hop spans (overflow
    counts the router registry's ``nxdi_traces_dropped_total``).
    """

    def __init__(self, **kwargs):
        self.degraded_penalty = float(kwargs.pop("degraded_penalty", 4.0))
        self.inflight_weight = float(kwargs.pop("inflight_weight", 1.0))
        self.shed_queue_depth = float(kwargs.pop("shed_queue_depth", 16.0))
        scf = kwargs.pop("shed_class_factors", None)
        if scf is None:
            scf = {"interactive": 2.0, "batch": 1.0, "best_effort": 0.5}
        self.shed_class_factors = {str(k): float(v) for k, v in dict(scf).items()}
        mf = kwargs.pop("max_failovers", None)
        self.max_failovers = None if mf is None else int(mf)
        self.stream_failures = int(kwargs.pop("stream_failures", 2))
        self.ingest_timeout_s = float(kwargs.pop("ingest_timeout_s", 5.0))
        self.poll_interval_s = float(kwargs.pop("poll_interval_s", 0.5))
        self.max_sessions = int(kwargs.pop("max_sessions", 4096))
        self.max_requests = int(kwargs.pop("max_requests", 4096))
        self.trace_sample_rate = float(kwargs.pop("trace_sample_rate", 1.0))
        self.trace_buffer = int(kwargs.pop("trace_buffer", 512))
        if kwargs:
            raise ValueError(f"Unknown RouterConfig args: {sorted(kwargs)}")
        if self.degraded_penalty < 0:
            raise ValueError("router degraded_penalty must be >= 0")
        if self.inflight_weight < 0:
            raise ValueError("router inflight_weight must be >= 0")
        if self.shed_queue_depth < 0:
            raise ValueError("router shed_queue_depth must be >= 0")
        if any(v <= 0 for v in self.shed_class_factors.values()):
            raise ValueError("router shed_class_factors must all be > 0")
        if self.max_failovers is not None and self.max_failovers < 0:
            raise ValueError("router max_failovers must be >= 0 (or None)")
        if self.stream_failures < 1:
            raise ValueError("router stream_failures must be >= 1")
        if self.ingest_timeout_s <= 0 or self.poll_interval_s <= 0:
            raise ValueError(
                "router ingest_timeout_s and poll_interval_s must be > 0"
            )
        if self.max_sessions < 1 or self.max_requests < 1:
            raise ValueError("router max_sessions/max_requests must be >= 1")
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError("router trace_sample_rate must be within [0, 1]")
        if self.trace_buffer < 1:
            raise ValueError("router trace_buffer must be >= 1")

    def to_dict(self):
        return dict(self.__dict__)


class FaultConfig:
    """Fault tolerance and recovery (nxdi_tpu/runtime/faults.py): the
    dispatch watchdog and the engine's step-fault recovery budget.

    ``watchdog`` — run every model dispatch on a watchdog worker thread
    with a per-program timeout of ``CostSheet floor × watchdog_multiplier``
    (clamped below by ``watchdog_min_timeout_s``); a timed-out launch trips
    the watchdog, counts as transient, and retries. Off by default — the
    worker-thread hop costs a context switch per dispatch.
    ``watchdog_multiplier`` / ``watchdog_min_timeout_s`` — the timeout
    formula's two knobs (floors come from the cost observatory; tags
    without a sheet use the bare minimum).
    ``max_retries`` — in-place transient-dispatch retries before the fault
    escapes to the engine step (each preceded by the deterministic backoff
    ``min(backoff_base_s * 2**attempt, backoff_max_s)``).
    ``max_recoveries`` — times one request may be requeued through the
    recompute-preemption path after a transient step fault before it
    error-finishes (the router then fails it over).
    """

    def __init__(self, **kwargs):
        self.watchdog = bool(kwargs.pop("watchdog", False))
        self.watchdog_multiplier = float(kwargs.pop("watchdog_multiplier", 20.0))
        self.watchdog_min_timeout_s = float(
            kwargs.pop("watchdog_min_timeout_s", 0.5)
        )
        self.max_retries = int(kwargs.pop("max_retries", 2))
        self.backoff_base_s = float(kwargs.pop("backoff_base_s", 0.05))
        self.backoff_max_s = float(kwargs.pop("backoff_max_s", 2.0))
        self.max_recoveries = int(kwargs.pop("max_recoveries", 3))
        if kwargs:
            raise ValueError(f"Unknown FaultConfig args: {sorted(kwargs)}")
        if self.watchdog_multiplier <= 0:
            raise ValueError("fault watchdog_multiplier must be > 0")
        if self.watchdog_min_timeout_s <= 0:
            raise ValueError("fault watchdog_min_timeout_s must be > 0")
        if self.max_retries < 0:
            raise ValueError("fault max_retries must be >= 0")
        if self.backoff_base_s <= 0 or self.backoff_max_s < self.backoff_base_s:
            raise ValueError(
                "fault backoff needs 0 < backoff_base_s <= backoff_max_s"
            )
        if self.max_recoveries < 0:
            raise ValueError("fault max_recoveries must be >= 0")

    def to_dict(self):
        return dict(self.__dict__)


class HybridShardingConfig:
    """Per-phase hybrid MoE TPxEP regimes (reference: models/config.py:1060
    ``HybridShardingConfig``). ``moe_cte_ep_degree`` experts-axis width for
    prefill (TP-heavy), ``moe_tkg_ep_degree`` for decode (EP-heavy); the
    per-phase moe-tp widths are the world divided by these. The tkg degree
    must be a multiple of the cte degree (the mesh refines ep into ep x epx)."""

    def __init__(self, **kwargs):
        self.moe_cte_ep_degree = int(kwargs.pop("moe_cte_ep_degree", 1))
        self.moe_tkg_ep_degree = int(kwargs.pop("moe_tkg_ep_degree", 1))
        if kwargs:
            raise ValueError(f"Unknown HybridShardingConfig args: {sorted(kwargs)}")
        if self.moe_cte_ep_degree < 1 or self.moe_tkg_ep_degree < 1:
            raise ValueError("hybrid sharding degrees must be >= 1")
        if self.moe_tkg_ep_degree % self.moe_cte_ep_degree:
            raise ValueError(
                f"moe_tkg_ep_degree ({self.moe_tkg_ep_degree}) must be a "
                f"multiple of moe_cte_ep_degree ({self.moe_cte_ep_degree}) — "
                "the mesh refines the cte ep axis into (ep, epx)"
            )

    def to_dict(self):
        return dict(self.__dict__)


class SpeculationConfig:
    """Speculative decoding knobs (reference: models/config.py:244-266)."""

    def __init__(self, **kwargs):
        self.speculation_length = kwargs.pop("speculation_length", 0)
        self.enable_fused_speculation = kwargs.pop("enable_fused_speculation", False)
        self.enable_eagle_speculation = kwargs.pop("enable_eagle_speculation", False)
        self.is_eagle3 = kwargs.pop("is_eagle3", False)
        self.is_eagle_draft = kwargs.pop("is_eagle_draft", False)
        self.token_tree_config = kwargs.pop("token_tree_config", None)
        if kwargs:
            raise ValueError(f"Unknown SpeculationConfig args: {sorted(kwargs)}")

    def to_dict(self):
        d = dict(self.__dict__)
        if self.token_tree_config is not None and hasattr(self.token_tree_config, "to_dict"):
            d["token_tree_config"] = self.token_tree_config.to_dict()
        return d


def promote_text_config(config) -> None:
    """Composite HF configs (llava, llama4, ...) nest the LM hyperparams under
    ``text_config``; promote them to the top level as the source of truth —
    the wrapper level carries PretrainedConfig defaults (e.g.
    tie_word_embeddings) that must NOT shadow the text values."""
    tc = getattr(config, "text_config", None)
    if tc is None:
        return
    if not isinstance(tc, dict):
        tc = tc.to_dict()
    for k, v in tc.items():
        setattr(config, k, v)


class TensorCaptureConfig:
    """Named intermediate tensors compiled into extra model outputs
    (reference: TensorCaptureConfig config.py:1085, model_base.py:1091-1198).

    ``capture_points``: any of "embeds" (post-embedding stream),
    "layer_hiddens" (every decoder layer's output, stacked (L, B, S, H)),
    "hidden" (pre-final-norm stream), "logits" (full-vocab logits)."""

    VALID = ("embeds", "layer_hiddens", "hidden", "logits")

    def __init__(self, **kwargs):
        pts = tuple(kwargs.pop("capture_points", ("hidden",)))
        for p in pts:
            if p not in self.VALID:
                raise ValueError(
                    f"unknown capture point {p!r}; valid: {self.VALID}"
                )
        self.capture_points = pts
        if kwargs:
            raise ValueError(f"Unknown TensorCaptureConfig args: {sorted(kwargs)}")

    def to_dict(self):
        return {"capture_points": list(self.capture_points)}


class TensorReplacementConfig:
    """Inject host-captured tensors INTO the device graph — tensor capture's
    plumbing in reverse (reference: utils/tensor_replacement/registry.py:1-50,
    config.py:1136-1166, model_wrapper.py:331-348: replay CPU-captured module
    outputs inside the compiled graph to bisect numeric divergence).

    TPU-native: each replacement point becomes an extra fixed-shape jitted
    input (zeros + a zero mask when unused, so one compiled program serves
    both plain and replaced runs). ``replace_points`` any of:
      - "embeds": replace the post-embedding stream with ``tr_embeds`` (B,S,H)
      - "layers": replace individual layers' output streams — inside the layer
        scan, ``where(tr_layer_mask[l], tr_layer_values[l], hidden)`` with
        ``tr_layer_values`` (B,L,S,H) and ``tr_layer_mask`` (L,) per row
      - "hidden": replace the pre-final-norm stream with ``tr_hidden`` (B,S,H)
    ("logits" is deliberately not a point: nothing downstream consumes it —
    capture the logits instead.)"""

    VALID = ("embeds", "layers", "hidden")

    def __init__(self, **kwargs):
        pts = tuple(kwargs.pop("replace_points", ("layers",)))
        for p in pts:
            if p not in self.VALID:
                raise ValueError(
                    f"unknown replacement point {p!r}; valid: {self.VALID}"
                )
        self.replace_points = pts
        if kwargs:
            raise ValueError(f"Unknown TensorReplacementConfig args: {sorted(kwargs)}")

    def to_dict(self):
        return {"replace_points": list(self.replace_points)}


class LoraServingConfig:
    """Multi-adapter LoRA serving (reference: modules/lora_serving/config.py)."""

    def __init__(self, **kwargs):
        self.max_loras = kwargs.pop("max_loras", 1)
        self.max_lora_rank = kwargs.pop("max_lora_rank", 16)
        self.lora_ckpt_paths = kwargs.pop("lora_ckpt_paths", None)  # {adapter_id: path}
        self.target_modules = kwargs.pop(
            "target_modules", ["q_proj", "k_proj", "v_proj", "o_proj"]
        )
        self.lora_dtype = kwargs.pop("lora_dtype", "bfloat16")
        self.lora_alpha = kwargs.pop("lora_alpha", 16.0)
        if kwargs:
            raise ValueError(f"Unknown LoraServingConfig args: {sorted(kwargs)}")

    def to_dict(self):
        return dict(self.__dict__)


class TpuConfig:
    """Runtime/feature configuration — the analog of the reference's NeuronConfig
    (reference: models/config.py:84-609). Field names are kept compatible where the
    concept transfers so users of the reference find what they expect.
    """

    def __init__(self, **kwargs) -> None:
        # --- basic shapes (reference: config.py:94-101) ---
        self.batch_size = kwargs.pop("batch_size", 1)
        self.padding_side = kwargs.pop("padding_side", "right")
        self.seq_len = kwargs.pop("seq_len", 128)
        self.n_active_tokens = kwargs.pop("n_active_tokens", self.seq_len)
        self.max_context_length = kwargs.pop("max_context_length", self.seq_len)
        self.max_new_tokens = kwargs.pop("max_new_tokens", None)
        self.max_length = kwargs.pop("max_length", self.seq_len)
        self.on_cpu = kwargs.pop("on_cpu", False)
        self.output_logits = kwargs.pop("output_logits", False)

        # --- dtypes ---
        self.dtype = to_jax_dtype(kwargs.pop("dtype", kwargs.pop("torch_dtype", "bfloat16")))
        self.attention_dtype = kwargs.pop("attention_dtype", None)
        if self.attention_dtype is not None:
            self.attention_dtype = to_jax_dtype(self.attention_dtype)
        self.rpl_reduce_dtype = kwargs.pop("rpl_reduce_dtype", None)  # row-parallel reduce dtype
        if self.rpl_reduce_dtype is not None:
            self.rpl_reduce_dtype = to_jax_dtype(self.rpl_reduce_dtype)
        self.cast_type = kwargs.pop("cast_type", "config")
        self.softmax_dtype = to_jax_dtype(kwargs.pop("softmax_dtype", "float32"))

        # --- batching (reference: config.py:162-171) ---
        self.ctx_batch_size = kwargs.pop("ctx_batch_size", self.batch_size)
        self.tkg_batch_size = kwargs.pop("tkg_batch_size", self.batch_size)
        self.max_batch_size = kwargs.pop("max_batch_size", self.batch_size)
        self.is_continuous_batching = kwargs.pop("is_continuous_batching", False)
        self.kv_cache_batch_size = kwargs.pop("kv_cache_batch_size", self.batch_size)
        self.kv_cache_padding_size = kwargs.pop("kv_cache_padding_size", 0)

        # --- sampling (reference: config.py:174-181) ---
        odsc = kwargs.pop("on_device_sampling_config", None)
        if isinstance(odsc, dict):
            odsc = OnDeviceSamplingConfig(**odsc)
        self.on_device_sampling_config = odsc

        # --- async (reference: config.py:184) — JAX dispatch is async by default; this
        # flag controls explicit double-buffered dispatch in the generation loop.
        self.async_mode = kwargs.pop("async_mode", False)

        # --- multi-step decode dispatch: ONE compiled program runs K token-
        # generation steps (sample -> embed -> layer stack -> KV commit chained
        # via lax.scan) per host dispatch, so the per-dispatch weight stream
        # amortizes over K tokens ("Kernel Looping" / ClusterFusion-style
        # collapse of per-step dispatch boundaries; see models/base.py
        # multi_step_token_gen). 1 = classic one-dispatch-per-token decode.
        self.decode_steps_per_dispatch = int(
            kwargs.pop("decode_steps_per_dispatch", 1)
        )

        # --- device-resident decode loop: compile the `tkg_device_loop`
        # submodel — a lax.while_loop running one full decode step per
        # iteration with per-row EOS + token-budget exit applied IN-GRAPH
        # (models/base.py device_loop_token_gen). The serving engine then
        # retires a batch's whole heterogeneous remaining budget in ONE
        # dispatch instead of a ladder of fixed-K scan windows.
        self.device_loop = bool(kwargs.pop("device_loop", False))
        # per-iteration device->host token out-feed (io_callback ring).
        # None = auto: ON for real accelerator backends, OFF on CPU/interpret
        # where the buffered whole-result path is the exact tier-1 surface.
        self.device_loop_outfeed = kwargs.pop("device_loop_outfeed", None)
        # upper bound on tokens per loop launch (0 = unlimited). A fence
        # forces the loop back to the host every N iterations so admission /
        # retirement / preemption get a scheduling point under load — the
        # "preemption fence" between resident-loop launches.
        self.device_loop_fence = int(kwargs.pop("device_loop_fence", 0))

        # --- bucketing (reference: config.py:187-208) ---
        self.enable_bucketing = kwargs.pop("enable_bucketing", False)
        self.buckets = kwargs.pop("buckets", None)
        self.bucket_n_active_tokens = kwargs.pop("bucket_n_active_tokens", False)
        self.context_encoding_buckets = kwargs.pop("context_encoding_buckets", None)
        self.token_generation_buckets = kwargs.pop("token_generation_buckets", None)
        self.prefix_buckets = kwargs.pop("prefix_buckets", None)

        # --- quantization (reference: config.py:217-241) ---
        self.quantized = kwargs.pop("quantized", False)
        self.quantized_checkpoints_path = kwargs.pop("quantized_checkpoints_path", None)
        self.quantization_dtype = kwargs.pop("quantization_dtype", "int8")
        self.quantization_type = kwargs.pop("quantization_type", "per_tensor_symmetric")
        self.modules_to_not_convert = kwargs.pop("modules_to_not_convert", None)
        kvq = kwargs.pop("kv_quant_config", None)
        if isinstance(kvq, dict):
            kvq = KVQuantizationConfig(**kvq)
        self.kv_quant_config = kvq
        self.kv_cache_quant = kwargs.pop("kv_cache_quant", False)
        if self.kv_cache_quant and self.kv_quant_config is None:
            self.kv_quant_config = KVQuantizationConfig()
        # activation quantization (reference: config.py:434-517): "dynamic"
        # computes per-token scales on the hot path; "static" reads calibrated
        # per-tensor input scales from the quantized checkpoint
        # (ops/quantization.calibrate_input_scales)
        self.activation_quantization_type = kwargs.pop("activation_quantization_type", None)
        if isinstance(self.activation_quantization_type, str):
            self.activation_quantization_type = self.activation_quantization_type.lower()
        self.quantize_clamp_bound = kwargs.pop("quantize_clamp_bound", None)
        if self.activation_quantization_type is not None:
            if self.activation_quantization_type not in ("dynamic", "static"):
                raise ValueError(
                    "activation_quantization_type: 'dynamic' or 'static' "
                    f"(got {self.activation_quantization_type!r})"
                )
            if not self.quantized or self.quantization_dtype != "int8":
                raise ValueError(
                    f"activation_quantization_type={self.activation_quantization_type!r} "
                    "requires quantized=True with quantization_dtype='int8' "
                    "(the int8 MXU path)"
                )

        # --- speculation (reference: config.py:244-272) ---
        spec = kwargs.pop("speculation_config", None)
        if isinstance(spec, dict):
            spec = SpeculationConfig(**spec)
        self.speculation_config = spec
        self.speculation_length = kwargs.pop(
            "speculation_length", spec.speculation_length if spec else 0
        )
        self.enable_fused_speculation = kwargs.pop(
            "enable_fused_speculation", spec.enable_fused_speculation if spec else False
        )
        self.enable_eagle_speculation = kwargs.pop(
            "enable_eagle_speculation", spec.enable_eagle_speculation if spec else False
        )
        if self.enable_eagle_speculation:
            self.enable_fused_speculation = True
        self.is_eagle3 = kwargs.pop("is_eagle3", spec.is_eagle3 if spec else False)
        self.is_eagle_draft = kwargs.pop("is_eagle_draft", False)
        # EAGLE token-tree speculation: medusa-style path list (reference:
        # modules/eagle/token_tree.py:8 TokenTree config)
        self.token_tree_config = kwargs.pop(
            "token_tree_config", spec.token_tree_config if spec else None
        )
        self.is_medusa = kwargs.pop("is_medusa", False)
        self.medusa_speculation_length = kwargs.pop("medusa_speculation_length", 0)
        self.num_medusa_heads = kwargs.pop("num_medusa_heads", 0)
        self.medusa_tree = kwargs.pop("medusa_tree", None)

        # --- paged / block KV (reference: config.py:278-283) ---
        self.is_block_kv_layout = kwargs.pop("is_block_kv_layout", False)
        self.pa_num_blocks = kwargs.pop("pa_num_blocks", None)
        self.pa_block_size = kwargs.pop("pa_block_size", 128)
        self.is_prefix_caching = kwargs.pop("is_prefix_caching", False)
        cpc = kwargs.pop("chunked_prefill_config", None)
        if isinstance(cpc, dict):
            cpc = ChunkedPrefillConfig(**cpc)
        self.chunked_prefill_config = cpc
        self.is_chunked_prefill = cpc is not None
        # unified mixed prefill+decode dispatch: compile the `mixed` packed
        # submodel (token-count bucket ladder) and let the serving engine
        # issue ONE program per step for a batch holding prefill chunks AND
        # decode rows together (ragged paged-attention kernel / XLA mask)
        self.mixed_dispatch = kwargs.pop("mixed_dispatch", False)
        # prefill/decode disaggregation (serving/handoff.py): which half of
        # the serving topology this process compiles.
        #   "unified" — every submodel the other flags ask for (default);
        #   "prefill" — CTE/prefix-prefill + the plain 1-token TKG only: the
        #     engine prefills, samples the first token, then parks the KV
        #     block chain for export to a decode replica;
        #   "decode"  — TKG/multistep/device-loop only (no CTE bucket
        #     ladder — a smaller HBM program footprint): requests enter via
        #     an imported KV chain, never a local prefill.
        self.role = kwargs.pop("role", "unified")

        # --- LoRA (reference: config.py:357-359) ---
        lora = kwargs.pop("lora_config", None)
        if isinstance(lora, dict):
            lora = LoraServingConfig(**lora)
        self.lora_config = lora

        # --- parallelism (reference: config.py:362-390) ---
        self.tp_degree = kwargs.pop("tp_degree", 1)
        self.cp_degree = kwargs.pop("cp_degree", 1)
        self.attention_dp_degree = kwargs.pop("attention_dp_degree", 1)
        self.pp_degree = kwargs.pop("pp_degree", 1)
        # microbatches per pipelined forward (GPipe rotation over the batch
        # dim; reference: pp_degree plumbed via NxD ModelBuilder,
        # application_base.py:158-163). 0 = use pp_degree.
        self.pp_microbatches = kwargs.pop("pp_microbatches", 0)
        self.ep_degree = kwargs.pop("ep_degree", 1)
        self.moe_tp_degree = kwargs.pop("moe_tp_degree", None)
        self.moe_ep_degree = kwargs.pop("moe_ep_degree", None)
        # per-phase hybrid MoE sharding (reference: HybridShardingConfig,
        # models/config.py:1060): prefill compiles TP-heavy (experts over a
        # small cte-ep axis), decode EP-heavy (experts over cte-ep x epx).
        # Expert weights are DUPLICATED per regime like the reference's
        # preshard hook (mlp_op_tkg duplication) — relayout-free at phase
        # transitions at the cost of one extra per-rank expert shard copy.
        hsc = kwargs.pop("hybrid_sharding_config", None)
        if isinstance(hsc, dict):
            hsc = HybridShardingConfig(**hsc)
        self.hybrid_sharding_config = hsc
        self.world_size = kwargs.pop("world_size", None)
        if self.world_size is None:
            self.world_size = self.tp_degree * self.pp_degree
        self.start_rank_id = kwargs.pop("start_rank_id", 0)
        self.sequence_parallel_enabled = kwargs.pop("sequence_parallel_enabled", False)
        # MLP-CP (reference: mlp_cp_degree config.py:364,374-375): without SP
        # this shards JUST the MLP block's stream on S (the mlp_hidden policy,
        # parallel/policy.py); with SP the whole inter-layer stream is already
        # S-sharded and the knob is subsumed.
        self.mlp_cp_degree = kwargs.pop("mlp_cp_degree", 1)
        self.flash_decoding_enabled = kwargs.pop("flash_decoding_enabled", False)
        self.num_cores_per_group = kwargs.pop("num_cores_per_group", 1)
        self.vocab_parallel = kwargs.pop("vocab_parallel", True)

        # --- kernels (reference: config.py:418-533). On TPU these gate Pallas kernels;
        # the XLA path is always available as fallback.
        self.attn_kernel_enabled = kwargs.pop("attn_kernel_enabled", None)
        self.attn_tkg_kernel_enabled = kwargs.pop("attn_tkg_kernel_enabled", False)
        self.attn_block_tkg_kernel_enabled = kwargs.pop("attn_block_tkg_kernel_enabled", False)
        self.fused_qkv = kwargs.pop("fused_qkv", False)
        self.qkv_kernel_enabled = kwargs.pop("qkv_kernel_enabled", False)
        self.mlp_kernel_enabled = kwargs.pop("mlp_kernel_enabled", False)
        self.k_cache_transposed = kwargs.pop("k_cache_transposed", False)

        # --- misc/debug ---
        self.qk_layernorm = kwargs.pop("qk_layernorm", False)
        self.sliding_window = kwargs.pop("sliding_window", None)
        # window-sized ring KV cache for uniformly sliding-window models
        # (reference: window-sized cache shapes kv_cache_manager.py:195-210):
        # cache S dim = sliding_window slots instead of seq_len
        self.window_sized_kv = kwargs.pop("window_sized_kv", False)
        # long-context mode (reference: enable_long_context_mode, derived at
        # >=32k — models/config.py:578-587 sets Neuron runtime/compiler modes;
        # the TPU analog coarsens the bucket ladders so 128k-class configs
        # don't compile a dozen huge CTE programs). Auto-on at 32k; override
        # explicitly to force either way.
        _lcm = kwargs.pop("long_context_mode", None)
        self.long_context_mode = (
            bool(_lcm) if _lcm is not None else self.seq_len >= 32 * 1024
        )
        self.windowed_context_encoding_size = kwargs.pop("windowed_context_encoding_size", None)
        self.logical_nc_config = kwargs.pop("logical_nc_config", 1)
        self.skip_warmup = kwargs.pop("skip_warmup", False)
        self.save_sharded_checkpoint = kwargs.pop("save_sharded_checkpoint", False)
        tcc = kwargs.pop("tensor_capture_config", None)
        if isinstance(tcc, dict):
            tcc = TensorCaptureConfig(**tcc)
        self.tensor_capture_config = tcc
        trc = kwargs.pop("tensor_replacement_config", None)
        if isinstance(trc, dict):
            trc = TensorReplacementConfig(**trc)
        self.tensor_replacement_config = trc
        # serving telemetry (nxdi_tpu/telemetry): always-on metrics registry
        # + request spans; accepts a TelemetryConfig, a dict of its kwargs, or
        # a detail-level string ("off" | "basic" | "full")
        tel = kwargs.pop("telemetry", None)
        if isinstance(tel, str):
            tel = TelemetryConfig(detail=tel)
        elif isinstance(tel, dict):
            tel = TelemetryConfig(**tel)
        elif tel is None:
            tel = TelemetryConfig()
        self.telemetry = tel
        # declared serving SLOs (nxdi_tpu/telemetry/slo.py): TTFT/TPOT
        # latency targets the SLO tracker measures attainment against and
        # the flight recorder's breach trigger fires on. An SloConfig, a
        # dict of its kwargs, or None (no SLO declared — nothing tracked).
        slo = kwargs.pop("slo", None)
        if isinstance(slo, dict):
            slo = SloConfig(**slo)
        self.slo = slo
        # QoS control plane, engine tier (nxdi_tpu/control/qos.py):
        # multi-tenant token-bucket quotas + deadline-aware admission and
        # preemption over priority classes. A QosConfig, a dict of its
        # kwargs, True (defaults), or None (off — admission stays FCFS/
        # cache-aware and output is byte-identical to previous rounds).
        qos = kwargs.pop("qos", None)
        if qos is True:
            qos = QosConfig()
        elif isinstance(qos, dict):
            qos = QosConfig(**qos)
        self.qos = qos
        # numerics sentinel (nxdi_tpu/telemetry/sentinel.py): in-graph
        # logit-health stats + sampled shadow-replay verification + the
        # preemption-replay invariant. A SentinelConfig, a dict of its
        # kwargs, True (defaults), or None (off — no stats compiled in,
        # serving output byte-identical to previous rounds).
        sentinel = kwargs.pop("sentinel", None)
        if sentinel is True:
            sentinel = SentinelConfig()
        elif isinstance(sentinel, dict):
            sentinel = SentinelConfig(**sentinel)
        self.sentinel = sentinel
        # fault tolerance (nxdi_tpu/runtime/faults.py): dispatch watchdog +
        # step-fault recovery budgets. A FaultConfig, a dict of its kwargs,
        # True (defaults), or None (defaults too — recovery is always on;
        # the config only tunes budgets and opts into the watchdog).
        faults = kwargs.pop("faults", None)
        if faults is True or faults is None:
            faults = FaultConfig()
        elif isinstance(faults, dict):
            faults = FaultConfig(**faults)
        self.faults = faults
        # declared chip generation for the cost observatory's roofline math
        # and the hbm_fit auditor checker (analysis/costs.py): a name from
        # CHIP_SPECS ("v4"|"v5e"|"v5p"|"v6e"), or a dict of ChipSpec field
        # overrides (optionally with "base": name). None = the attached TPU,
        # v5e where none is attached; a declaration that names another part
        # than the attached one raises when the chip is resolved.
        self.chip = kwargs.pop("chip", None)
        # serve-time retrace guard (analysis/retrace.py): "warn" logs and
        # "error" raises when any submodel program lowers AFTER warmup sealed
        # the program set (a mid-serving retrace blocks requests on multi-
        # second compilation); "off" disables recording enforcement.
        self.retrace_guard = kwargs.pop("retrace_guard", "warn")
        self.allow_unknown = kwargs.pop("allow_unknown", False)

        self.is_prefill_stage = None  # set by enable_context_encoding/token_generation

        if kwargs and not self.allow_unknown:
            raise ValueError(f"Unknown TpuConfig arguments: {sorted(kwargs)}")
        self.validate()

    # -- validation (reference: config.py:611-687 does similar cross-checks) --
    def validate(self) -> None:
        if self.padding_side not in ("right", "left"):
            raise ValueError("padding_side must be 'right' or 'left'")
        if self.retrace_guard not in ("off", "warn", "error"):
            raise ValueError(
                f"retrace_guard must be 'off'|'warn'|'error', got {self.retrace_guard!r}"
            )
        if self.chip is not None:
            if not isinstance(self.chip, (str, dict)):
                raise ValueError(
                    "chip must be a chip name or a dict of ChipSpec overrides "
                    f"(analysis/costs.py CHIP_SPECS), got {type(self.chip)}"
                )
            # resolve eagerly so a typo'd name/field fails HERE, not inside a
            # swallowed export attachment or an auditor checker at serve time
            from nxdi_tpu.analysis.costs import declared_chip

            try:
                declared_chip(self.chip)
            except (TypeError, ValueError) as e:
                raise ValueError(f"invalid TpuConfig chip={self.chip!r}: {e}")
        if self.max_context_length > self.seq_len:
            raise ValueError(
                f"max_context_length ({self.max_context_length}) cannot exceed seq_len ({self.seq_len})"
            )
        if self.cp_degree > 1 and self.tp_degree % self.cp_degree != 0:
            raise ValueError("cp_degree must divide tp_degree (CP splits the TP world)")
        if self.attention_dp_degree > 1:
            if self.tp_degree % (self.attention_dp_degree * self.cp_degree) != 0:
                raise ValueError(
                    "attention_dp_degree * cp_degree must divide tp_degree "
                    "(both carve sub-axes out of the TP world)"
                )
            if self.tkg_batch_size % self.attention_dp_degree != 0:
                raise ValueError("tkg_batch_size must be divisible by attention_dp_degree")
        if self.flash_decoding_enabled:
            if self.attention_dp_degree > 1:
                raise ValueError(
                    "flash_decoding_enabled and attention_dp_degree > 1 are "
                    "mutually exclusive: both claim the decode KV cache layout"
                )
            if self.cp_degree <= 1:
                raise ValueError(
                    "flash_decoding_enabled shards the KV cache sequence dim over "
                    "the cp mesh axis; set cp_degree > 1"
                )
            if self.enable_bucketing or self.token_generation_buckets:
                raise ValueError(
                    "flash decoding requires a single token-generation bucket: "
                    "the cache sequence dim is sharded and cannot be re-windowed "
                    "per bucket"
                )
        if self.pp_degree > 1:
            n_micro = self.pp_microbatches or self.pp_degree
            if self.is_block_kv_layout:
                raise ValueError(
                    "pipeline parallel composes with the contiguous KV layout "
                    "only (the paged pool is not batch-addressable per stage)"
                )
            if self.flash_decoding_enabled or self.attention_dp_degree > 1 or self.cp_degree > 1:
                raise ValueError(
                    "pipeline parallel currently composes with tp/sp only "
                    "(cp / attention-dp / flash-decoding also reshard the "
                    "batch or cache dims the pipeline microbatches over)"
                )
            for name, bs in (("batch_size", self.batch_size),
                             ("ctx_batch_size", self.ctx_batch_size),
                             ("tkg_batch_size", self.tkg_batch_size)):
                if bs and bs % n_micro != 0:
                    raise ValueError(
                        f"{name} ({bs}) must be divisible by pp_microbatches ({n_micro})"
                    )
        if self.hybrid_sharding_config is not None:
            hsc = self.hybrid_sharding_config
            if self.moe_ep_degree and self.moe_ep_degree > 1:
                raise ValueError(
                    "hybrid_sharding_config replaces moe_ep_degree (the mesh "
                    "ep/epx axes come from the per-phase degrees)"
                )
            if self.tp_degree % hsc.moe_tkg_ep_degree:
                raise ValueError(
                    f"moe_tkg_ep_degree ({hsc.moe_tkg_ep_degree}) must divide "
                    f"tp_degree ({self.tp_degree})"
                )
        kvq = self.kv_quant_config
        if kvq is not None and kvq.scale_mode in ("per_key", "per_channel"):
            if self.is_block_kv_layout or self.window_sized_kv:
                raise ValueError(
                    f"kv quant scale_mode={kvq.scale_mode!r} composes with the "
                    "contiguous KV layout only (paged/ring layouts take "
                    "per-tensor scales)"
                )
            if self.pp_degree > 1:
                raise ValueError(
                    f"kv quant scale_mode={kvq.scale_mode!r} is not supported "
                    "under pipeline parallel yet (per-layer scale indexing "
                    "needs the in-scan layer index)"
                )
        # fused projection kernels (reference: fused_qkv gqa.py:557, "QKV
        # kernel only supported when fused_qkv is TRUE" gqa.py:669) — these
        # flags either engage their kernels or raise; never a silent no-op
        if self.qkv_kernel_enabled and not self.fused_qkv:
            raise ValueError(
                "qkv_kernel_enabled requires fused_qkv=True (the kernel runs "
                "over the fused interleaved QKV weight)"
            )
        if self.fused_qkv and self.lora_config is not None:
            raise ValueError(
                "fused_qkv does not compose with LoRA serving (adapters "
                "target the separate q/k/v projections)"
            )
        if self.mlp_kernel_enabled and self.lora_config is not None:
            raise ValueError(
                "mlp_kernel_enabled does not compose with LoRA serving"
            )
        if self.mlp_kernel_enabled and self.quantized:
            raise ValueError(
                "mlp_kernel_enabled composes with full-precision weights only "
                "for now (quantized fused MLP is not implemented)"
            )
        if (self.mlp_kernel_enabled or self.qkv_kernel_enabled) and (
            self.window_sized_kv or self.pp_degree > 1
        ):
            # those paths scan without the stacked-weight extraction, so the
            # kernels would silently pay a per-layer weight slice copy
            raise ValueError(
                "mlp_kernel_enabled/qkv_kernel_enabled are not supported with "
                "window_sized_kv or pipeline parallel yet"
            )
        if self.window_sized_kv:
            if not self.sliding_window:
                raise ValueError(
                    "window_sized_kv needs tpu_config.sliding_window (the ring "
                    "slot count) — set it to the model's sliding window"
                )
            if self.sliding_window > self.seq_len:
                raise ValueError(
                    f"window_sized_kv ring ({self.sliding_window} slots) cannot "
                    f"exceed seq_len ({self.seq_len}) — the ring layout would "
                    "address slots the cache does not have"
                )
            if self.is_block_kv_layout:
                # under the block layout a window layer's rows are no option:
                # a family whose architecture has window layers beside full
                # ones keeps them as ring rows a SLOT beside the pool by itself
                # (models/mimo_v2); a ring the user sizes is the contiguous
                # layout's
                raise ValueError(
                    "window_sized_kv is a user-set ring of the contiguous "
                    "layout; under is_block_kv_layout leave it off: a family "
                    "with window layers beside full ones keeps their rows per "
                    "slot beside the block pool by itself, and a model whose "
                    "every layer is a window layer has no paged ring yet"
                )
            if (
                self.is_medusa
                or self.is_prefix_caching
                or self.is_chunked_prefill
                or self.flash_decoding_enabled
            ):
                raise ValueError(
                    "window_sized_kv composes with contiguous decode (and "
                    "linear speculation) only: medusa/prefix modes "
                    "assume position-addressed cache slots, which the ring "
                    "layout does not provide"
                )
            if self.speculation_length > 0 or self.enable_fused_speculation:
                # linear speculation over a ring: the ring is over-provisioned
                # by spec_len+1 slots so rejected-draft writes can never
                # clobber a slot still inside any query's attention window,
                # and stale rejected rows always resolve to out-of-window
                # positions (see kvcache WindowKVLayout docstring)
                if self.window_ring_slots > self.seq_len:
                    raise ValueError(
                        f"window_sized_kv + speculation needs sliding_window +"
                        f" speculation_length + 1 = {self.window_ring_slots} "
                        f"ring slots, which exceeds seq_len ({self.seq_len})"
                    )
        if self.mlp_cp_degree and self.mlp_cp_degree > 1:
            # without SP this engages the dedicated MLP-CP policy (only the
            # MLP stream shards on S — parallel/policy.py mlp_hidden); with
            # SP the whole inter-layer stream is already S-sharded and the
            # knob is subsumed. GSPMD shards S over the FULL model-parallel
            # axis — partial subgroup S-sharding has no mesh sub-axis to
            # land on, so intermediate degrees are rejected loudly rather
            # than silently promoted.
            if self.mlp_cp_degree != self.tp_degree:
                raise ValueError(
                    f"mlp_cp_degree ({self.mlp_cp_degree}) must equal "
                    f"tp_degree ({self.tp_degree}) (or 1): the MLP-CP policy "
                    "shards the MLP stream's S dim over the whole tp axis"
                )
        if self.is_medusa and self.num_medusa_heads <= 0:
            raise ValueError("is_medusa requires num_medusa_heads > 0")
        if self.lora_config is not None and self.async_mode:
            raise ValueError(
                "LoRA serving is incompatible with async_mode: the device-"
                "resident decode loop cannot carry per-request adapter_ids"
            )
        if self.lora_config is not None and (
            self.enable_fused_speculation or self.is_medusa or self.speculation_length > 0
        ):
            raise ValueError(
                "LoRA serving is not supported with speculative decoding yet: "
                "the speculation graphs do not thread adapter_ids"
            )
        if self.speculation_length < 0:
            raise ValueError("speculation_length must be >= 0")
        if self.decode_steps_per_dispatch < 1:
            raise ValueError("decode_steps_per_dispatch must be >= 1")
        if self.decode_steps_per_dispatch > 1:
            # the K-step scan samples, advances positions, and commits KV
            # in-graph — host-side sampling / speculative strides / per-step
            # host inputs cannot ride inside it
            if self.on_device_sampling_config is None:
                raise ValueError(
                    "decode_steps_per_dispatch > 1 requires on-device sampling "
                    "(the K-step scan samples each token in-graph)"
                )
            if (
                self.enable_fused_speculation
                or self.is_medusa
                or self.speculation_length > 0
            ):
                raise ValueError(
                    "decode_steps_per_dispatch > 1 and speculative decoding "
                    "both own the token-generation stride; enable one"
                )
            if self.is_block_kv_layout:
                raise ValueError(
                    "decode_steps_per_dispatch > 1 needs in-graph KV "
                    "addressing by position; the block layout's slot mappings "
                    "are host-computed per step"
                )
            if self.lora_config is not None:
                raise ValueError(
                    "decode_steps_per_dispatch > 1 does not thread per-request "
                    "adapter_ids through the in-graph decode scan yet"
                )
            if (
                self.tensor_capture_config is not None
                or self.tensor_replacement_config is not None
            ):
                raise ValueError(
                    "decode_steps_per_dispatch > 1 does not compose with "
                    "tensor capture/replacement (per-step host tensors cannot "
                    "ride the in-graph scan)"
                )
            if self.ctx_batch_size != self.tkg_batch_size:
                # windows chain device-resident off the CTE's next_inputs
                # (already padded to the CTE batch), so both programs must
                # share one compiled batch — the same invariant the async
                # 1-step chain enforces (application.async_supported)
                raise ValueError(
                    "decode_steps_per_dispatch > 1 requires ctx_batch_size == "
                    "tkg_batch_size (the K-step windows chain device-resident "
                    "from the context-encoding outputs)"
                )
        if self.device_loop_fence < 0:
            raise ValueError("device_loop_fence must be >= 0 (0 = unlimited)")
        if self.device_loop:
            # the while-loop body samples, advances positions, and commits KV
            # in-graph — the same closed-world contract as the K-step scan,
            # plus a data-dependent trip count no host input can ride inside
            if self.on_device_sampling_config is None:
                raise ValueError(
                    "device_loop requires on-device sampling (the loop body "
                    "samples each token in-graph)"
                )
            if (
                self.enable_fused_speculation
                or self.is_medusa
                or self.speculation_length > 0
            ):
                raise ValueError(
                    "device_loop and speculative decoding both own the "
                    "token-generation stride; enable one"
                )
            if self.is_block_kv_layout:
                raise ValueError(
                    "device_loop needs in-graph KV addressing by position; "
                    "the block layout's slot mappings are host-computed per "
                    "step"
                )
            if self.lora_config is not None:
                raise ValueError(
                    "device_loop does not thread per-request adapter_ids "
                    "through the in-graph decode loop yet"
                )
            if (
                self.tensor_capture_config is not None
                or self.tensor_replacement_config is not None
            ):
                raise ValueError(
                    "device_loop does not compose with tensor capture/"
                    "replacement (per-step host tensors cannot ride the "
                    "in-graph loop)"
                )
            if self.ctx_batch_size != self.tkg_batch_size:
                raise ValueError(
                    "device_loop requires ctx_batch_size == tkg_batch_size "
                    "(loop launches share the decode batch the CTE filled)"
                )
            if self.mixed_dispatch:
                raise ValueError(
                    "device_loop and mixed_dispatch are different serving "
                    "step shapes (resident decode loop vs one packed "
                    "prefill+decode program); enable one"
                )
        if self.is_block_kv_layout and self.pa_num_blocks is None:
            self.pa_num_blocks = max(
                1, (self.seq_len * self.max_batch_size) // self.pa_block_size
            )
        if self.is_prefix_caching and not self.is_block_kv_layout:
            raise ValueError("is_prefix_caching requires is_block_kv_layout")
        if self.is_chunked_prefill and not self.is_block_kv_layout:
            raise ValueError("chunked prefill requires is_block_kv_layout")
        if self.mixed_dispatch and not self.is_block_kv_layout:
            raise ValueError(
                "mixed_dispatch requires is_block_kv_layout (the packed rows "
                "read KV through the paged block tables)"
            )
        if self.role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be 'unified', 'prefill' or 'decode', got {self.role!r}"
            )
        if self.role != "unified":
            if not self.is_block_kv_layout:
                raise ValueError(
                    f"role={self.role!r} requires is_block_kv_layout (the KV "
                    "handoff plane exports/imports paged block chains)"
                )
            if self.mixed_dispatch:
                raise ValueError(
                    "mixed_dispatch is inherently a unified prefill+decode "
                    f"program; it cannot compose with role={self.role!r}"
                )
            if self.role == "prefill" and (
                self.decode_steps_per_dispatch > 1 or self.device_loop
            ):
                raise ValueError(
                    "role='prefill' ships only CTE/prefix-prefill + a 1-token "
                    "TKG; decode_steps_per_dispatch > 1 and device_loop are "
                    "decode-role program shapes"
                )

    # -- (de)serialization (reference: config.py:891-1002) --
    _SUBCONFIGS = {
        "on_device_sampling_config": OnDeviceSamplingConfig,
        "kv_quant_config": KVQuantizationConfig,
        "chunked_prefill_config": ChunkedPrefillConfig,
        "tensor_capture_config": TensorCaptureConfig,
        "tensor_replacement_config": TensorReplacementConfig,
        "speculation_config": SpeculationConfig,
        "lora_config": LoraServingConfig,
        "hybrid_sharding_config": HybridShardingConfig,
        "telemetry": TelemetryConfig,
        "slo": SloConfig,
        "sentinel": SentinelConfig,
        "faults": FaultConfig,
    }

    @property
    def window_ring_slots(self) -> int:
        """Slot count of the window-sized ring stacks. Plain decode rings
        hold exactly ``sliding_window`` slots; under linear speculation the
        ring is over-provisioned by the spec window (spec_len + 1) so
        rejected-draft writes land in slots whose previous occupants are
        already outside every query's attention window."""
        lookahead = (
            self.speculation_length + 1 if self.speculation_length > 0 else 0
        )
        return int(self.sliding_window or 0) + lookahead

    def to_dict(self) -> Dict[str, Any]:
        out = {}
        derived = ("is_prefill_stage", "allow_unknown", "is_chunked_prefill")
        for k, v in self.__dict__.items():
            if k in derived:
                continue
            if k in self._SUBCONFIGS:
                out[k] = v.to_dict() if v is not None else None
            elif k in ("dtype", "attention_dtype", "rpl_reduce_dtype", "softmax_dtype"):
                out[k] = dtype_name(v) if v is not None else None
            else:
                out[k] = v
        return out

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TpuConfig":
        return cls(**{k: v for k, v in dict(d).items() if v is not None or k.endswith("_config")})

    def copy(self, **overrides) -> "TpuConfig":
        d = self.to_dict()
        d.update(overrides)
        return TpuConfig.from_dict(d)


class InferenceConfig:
    """Model hyperparameters + a :class:`TpuConfig` (reference: models/config.py:813).

    ``load_config`` is a callable returning a dict of hyperparameters — typically
    :func:`nxdi_tpu.generation.hf_adapter.load_pretrained_config` wrapping a HF
    ``config.json`` (reference: utils/hf_adapter.py:36).
    """

    # attributes that must exist after construction (reference: config.py:841-858)
    REQUIRED = ["hidden_size", "num_attention_heads", "num_hidden_layers", "vocab_size"]

    def __init__(self, tpu_config: TpuConfig, load_config=None, metadata=None, **kwargs):
        self.tpu_config = tpu_config
        self.metadata = metadata or {}
        if load_config is not None:
            for k, v in load_config().items():
                setattr(self, k, v)
        for k, v in kwargs.items():
            setattr(self, k, v)
        self.add_derived_config()
        self.validate_config()

    # subclasses override (reference: config.py:860-888)
    def add_derived_config(self) -> None:
        if not hasattr(self, "num_key_value_heads") and hasattr(self, "num_attention_heads"):
            self.num_key_value_heads = self.num_attention_heads
        if not hasattr(self, "head_dim") and hasattr(self, "hidden_size"):
            self.head_dim = self.hidden_size // self.num_attention_heads

    def get_required_attributes(self) -> List[str]:
        return list(self.REQUIRED)

    def validate_config(self) -> None:
        missing = [a for a in self.get_required_attributes() if not hasattr(self, a)]
        if missing:
            raise ValueError(f"InferenceConfig missing required attributes: {missing}")

    # -- JSON round trip (reference: config.py:891-1002) --
    def to_dict(self) -> Dict[str, Any]:
        out = {}
        for k, v in self.__dict__.items():
            if k == "tpu_config":
                out[k] = v.to_dict()
            elif k == "fused_spec_config" and v is not None:
                out[k] = v.to_dict() if hasattr(v, "to_dict") else v
            else:
                try:
                    json.dumps(v)
                    out[k] = v
                except TypeError:
                    continue  # non-serializable helper attrs are reconstructable
        return out

    def save(self, model_path: str) -> str:
        os.makedirs(model_path, exist_ok=True)
        path = os.path.join(model_path, CONFIG_FILE)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
        return path

    @classmethod
    def load(cls, model_path: str, **kwargs) -> "InferenceConfig":
        with open(os.path.join(model_path, CONFIG_FILE)) as f:
            d = json.load(f)
        tpu_config = TpuConfig.from_dict(d.pop("tpu_config"))
        obj = cls.__new__(cls)
        obj.tpu_config = tpu_config
        obj.metadata = {}
        for k, v in d.items():
            setattr(obj, k, v)
        for k, v in kwargs.items():
            setattr(obj, k, v)
        obj.add_derived_config()
        obj.validate_config()
        return obj


class FusedSpecConfig:
    """Pairs a draft model config with the target for fused speculation
    (reference: models/config.py:1009 ``FusedSpecNeuronConfig``)."""

    def __init__(self, worker_cls_name: str, draft_config: InferenceConfig, draft_model_path: str):
        self.worker_cls_name = worker_cls_name
        self.draft_config = draft_config
        self.draft_model_path = draft_model_path

    def to_dict(self):
        return {
            "worker_cls_name": self.worker_cls_name,
            "draft_config": self.draft_config.to_dict(),
            "draft_model_path": self.draft_model_path,
        }
