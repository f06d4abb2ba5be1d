"""Request lifecycle for the continuous-batching serving engine.

The reference stack delegates this layer to vLLM (NxDI only consumes block
tables and seq_ids); here it is first-class. A :class:`Request` is one
generation job with a WAITING -> RUNNING -> (PREEMPTED ->) FINISHED
lifecycle:

- WAITING   — queued FCFS; no device state.
- RUNNING   — holds an engine slot; prompt (re)prefill may still be in
  flight (``num_prefilled < len(seq_tokens)`` under chunked prefill).
- PREEMPTED — evicted on KV-pool exhaustion (recompute-style: its blocks
  are freed and the whole ``prompt + generated`` sequence is re-prefilled
  on re-admission — exact for greedy sampling).
- FINISHED  — EOS sampled or ``max_new_tokens`` reached; slot recycled.

:class:`SamplingParams` is the shared sampling-params plumbing: both the
static :class:`~nxdi_tpu.generation.hf_adapter.HuggingFaceGenerationAdapter`
and the engine build their per-row ``(top_k, top_p, temperature)`` tensors
through :meth:`SamplingParams.tensor`, so the two paths can never encode
greedy/sampled rows differently. It LIVES in :mod:`nxdi_tpu.ops.sampling`
(a leaf module, re-exported here) so the static adapter shares it without
importing the serving stack.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

from nxdi_tpu.ops.sampling import SamplingParams, normalize_eos_ids

__all__ = [
    "Request",
    "RequestOutput",
    "SamplingParams",
    "normalize_eos_ids",
    "WAITING",
    "RUNNING",
    "PREEMPTED",
    "FINISHED",
    "STATES",
]

# lifecycle states (str constants, not Enum: they serialize as-is)
WAITING = "WAITING"
RUNNING = "RUNNING"
PREEMPTED = "PREEMPTED"
FINISHED = "FINISHED"

STATES = (WAITING, RUNNING, PREEMPTED, FINISHED)


class Request:
    """One generation request inside the engine."""

    _ids = iter(range(1, 1 << 62))

    def __init__(
        self,
        prompt: Sequence[int],
        params: Optional[SamplingParams] = None,
        request_id: Optional[int] = None,
        on_token: Optional[Callable[["Request", int], None]] = None,
        arrival_s: Optional[float] = None,
        session_id: Optional[str] = None,
        trace=None,
    ):
        self.request_id = (
            int(request_id) if request_id is not None else next(Request._ids)
        )
        self.prompt: List[int] = [int(t) for t in prompt]
        if not self.prompt:
            raise ValueError("empty prompt")
        self.params = params or SamplingParams()
        self.on_token = on_token
        self.arrival_s = time.perf_counter() if arrival_s is None else arrival_s
        #: conversation identity for the router tier's session affinity
        #: (nxdi_tpu/router): requests sharing a session_id keep hitting the
        #: same replica's warm KV/prefix state while it stays dispatchable.
        #: First-class even off-router so spans carry it end to end.
        self.session_id = None if session_id is None else str(session_id)
        #: distributed-trace context (telemetry/tracing.py TraceContext or
        #: None): the parent for the hop spans this request records next
        #: (engine.prefill, handoff.export, ...). Requests admitted outside
        #: the routed plane carry None and record no hops.
        self.trace = trace
        #: wall-clock stamp of engine admission — the start of the
        #: engine-side hop spans (hop spans join across processes, so they
        #: ride the wall clock, not the telemetry clock)
        self.trace_t0 = time.time() if trace is not None else None

        self.state = WAITING
        self.generated: List[int] = []
        #: tokens dispatched for this request and not yet emitted: a decode
        #: program the engine has enqueued and not collected (at most one
        #: between steps). The request's next position and block growth
        #: (``dispatched_len``) and its budget (``remaining``) count them;
        #: leaving the slot (retire, preempt) zeroes it, and the engine drops
        #: such a token at its collect
        self.pending = 0
        #: committed tokens of the (re)prefill replay (chunked-prefill
        #: progress); complete when it reaches ``prefill_target``, which the
        #: scheduler pins to ``len(seq_tokens)`` at placement time (the
        #: sequence keeps growing during decode, the replay target must not)
        self.num_prefilled = 0
        self.prefill_target = 0
        self.slot: Optional[int] = None
        self.preemptions = 0
        #: step-fault recoveries consumed (requeues through the preemption
        #: path after a transient engine fault); error-finishes past
        #: ``FaultConfig.max_recoveries``
        self.recoveries = 0
        #: human-readable failure detail for ``finish_reason == "error"``
        self.error: Optional[str] = None
        #: telemetry-clock stamp of the last fault requeue; cleared (and
        #: turned into a resume-latency sample) on re-admission
        self._recovered_at: Optional[float] = None
        #: telemetry-clock stamp of the last (re)entry into the waiting
        #: queue — the scheduler's starvation bound for cache-aware
        #: admission reads queue age from it
        self.queued_s: Optional[float] = None
        # "eos" | "length" | "error" (un-resumable after preemption)
        self.finish_reason: Optional[str] = None
        self.span = None  # telemetry RequestSpan (engine-owned)
        self._admit_seq = -1  # admission order; youngest = max
        #: live parent Request this one is an ``n > 1`` continuation of:
        #: admission forks the parent's prompt KV blocks (COW) instead of
        #: re-prefilling; cleared when the parent is no longer forkable
        self.fork_of: Optional["Request"] = None
        #: stable parent id for output grouping (survives fork_of clearing)
        self.fork_parent_id: Optional[int] = None

    # -- derived views ------------------------------------------------------
    @property
    def tenant_id(self) -> Optional[str]:
        """QoS tenant identity (rides SamplingParams like ``n`` — host-side
        only; None = the control plane's default tenant)."""
        return self.params.tenant_id

    @property
    def priority(self) -> Optional[str]:
        """QoS priority class (``interactive`` | ``batch`` | ``best_effort``;
        None = the control plane's default class)."""
        return self.params.priority

    @property
    def seq_tokens(self) -> List[int]:
        """The full sequence a (re)prefill must commit: prompt + generated.
        A preempted request replays all of it (recompute-style resume)."""
        return self.prompt + self.generated

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.generated)

    @property
    def dispatched_len(self) -> int:
        """``total_len`` with the tokens in flight counted: the next decode
        feeds (and writes the KV of) position ``dispatched_len - 1``."""
        return self.total_len + self.pending

    @property
    def remaining(self) -> int:
        return self.params.max_new_tokens - len(self.generated) - self.pending

    @property
    def prefill_done(self) -> bool:
        return self.prefill_target > 0 and self.num_prefilled >= self.prefill_target

    @property
    def is_finished(self) -> bool:
        return self.state == FINISHED

    # -- engine-side transitions -------------------------------------------
    def emit(self, token: int) -> None:
        """Append one generated token and fire the streaming callback."""
        token = int(token)
        self.generated.append(token)
        if self.on_token is not None:
            self.on_token(self, token)

    def check_finish(self) -> Optional[str]:
        """Finish reason after the latest emitted token, else None."""
        if self.generated and self.generated[-1] in self.params.eos_token_ids:
            return "eos"
        if len(self.generated) >= self.params.max_new_tokens:
            return "length"
        return None

    def __repr__(self) -> str:
        sess = "" if self.session_id is None else f", session={self.session_id}"
        return (
            f"Request(id={self.request_id}, state={self.state}, "
            f"prompt={len(self.prompt)}t, generated={len(self.generated)}t, "
            f"slot={self.slot}, preemptions={self.preemptions}{sess})"
        )


@dataclass
class RequestOutput:
    """What the engine returns when a request finishes."""

    request_id: int
    prompt: List[int]
    token_ids: List[int]  # generated tokens only
    finish_reason: str
    metrics: dict = field(default_factory=dict)
    #: failure detail when ``finish_reason == "error"`` (None otherwise);
    #: the router keys failover off its engine-fault prefix
    error: Optional[str] = None

    @property
    def full_ids(self) -> List[int]:
        return list(self.prompt) + list(self.token_ids)

    def to_dict(self) -> dict:
        return {
            "request_id": self.request_id,
            "prompt": list(self.prompt),
            "token_ids": list(self.token_ids),
            "finish_reason": self.finish_reason,
            "metrics": dict(self.metrics),
            "error": self.error,
        }
