"""What one run leaves behind for the metric readers, and the arithmetic they
share. A reader (``end_to_end/<name>.py``, ``layer_metrics/<name>.py``) is
``read(run) -> number or None``; None leaves the metric out of the line.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Exact linear-interpolated percentile (numpy's default rule; a copy of
    ``telemetry.registry.percentile_exact``); None on no samples."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * min(max(p, 0.0), 100.0) / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


@dataclass
class Served:
    """One offered request as the benchmark saw it (clock: the engine's)."""

    index: int
    due: float
    offered: float
    prompt_len: int
    want_new: int
    request: object  # the engine's Request (holds .generated while it runs)
    output: object = None  # RequestOutput once finished
    finished_at: Optional[float] = None
    fault: Optional[str] = None  # why it counts as failed, if it does
    #: when each output token reached the streaming callback (``on_token``)
    token_times: List[float] = field(default_factory=list)
    prompt: Sequence[int] = ()  # as the generator drew it (the reference reads this one)


def fault_of(served: Served, vocab: int) -> Optional[str]:
    """Why a FINISHED request counts as failed, else None: every stream runs
    to its drawn length, ends with reason ``length``, and holds ids of the
    vocabulary (``eos_token_ids`` is empty, so an early end is a fault)."""
    out = served.output
    if out is None:
        return "not finished"
    if out.error or out.finish_reason != "length":
        return f"finish reason {out.finish_reason!r} ({out.error})"
    if len(out.token_ids) != served.want_new:
        return f"{len(out.token_ids)} tokens, drew {served.want_new}"
    if not all(0 <= t < vocab for t in out.token_ids):
        return "token id outside the vocabulary"
    return None


@dataclass
class RunRecords:
    """Everything the readers may read. Times are on the engine's clock."""

    seconds: float
    t_open: float
    t_close: float
    #: end of the part the host-side readers use: the window's close, or in a
    #: traced run the moment the profiler was started (tracing slows the host)
    t_host_end: float
    setup_s: float
    served: List[Served]  # every request offered, in offer order
    #: the requests the latency metrics are over: all offered in the window
    #: where in-flight work drains, those finished inside it where it is dropped
    population: List[Served]
    tokens_in_window: int
    steps: list  # flight StepRecords with t_start >= t_open and t_end <= t_host_end
    counters: Dict[str, float]  # registry counter deltas over [t_open, t_host_end]
    slots: int
    pool_blocks: int
    block_size: int
    tp: int
    config: dict
    traffic: dict
    device_kind: str
    trace: Optional[object] = None  # trace_reduce.TraceSummary in a traced run
    notes: Dict[str, float] = field(default_factory=dict)

    def ok(self) -> List[Served]:
        return [s for s in self.population if s.fault is None and s.output is not None]

    def metric_of_ok(self, key: str) -> List[float]:
        return [
            s.output.metrics[key] for s in self.ok()
            if s.output.metrics.get(key) is not None
        ]

    def token_gaps(self) -> List[float]:
        """Every gap between two consecutive output tokens of one stream, both
        inside ``[t_open, t_host_end]``: all streams, finished or still running,
        and nothing of the ramp before the window or the drain after it."""
        return [b - a for a, b in self._token_pairs()]

    def _token_pairs(self):
        """``(a, b)``: the times of two consecutive tokens of one clean stream,
        both inside ``[t_open, t_host_end]``."""
        for s in self.served:
            if s.fault is not None:
                continue
            inside = [t for t in s.token_times if self.t_open <= t <= self.t_host_end]
            yield from zip(inside, inside[1:])

    def gap_modes(self, buckets: Sequence[int]) -> List[dict]:
        """The token gaps by the engine step that ended them: a decode-only
        step (``decode``) or one that also ran a prefill of a prompt bucket
        (``cte<bucket>``). A gap tail is a percentile of these few modes, so
        each comes with its count, its share of all gaps and its range, in
        ascending order of its median gap; ``other`` holds what no step of the
        window's records ends (none, as a rule)."""
        import bisect

        steps = sorted((r for r in self.steps if r.decode is not None), key=lambda r: r.t_end)
        ends = [r.t_end for r in steps]
        buckets = sorted(buckets)
        found: Dict[str, List[float]] = {}
        for a, b in self._token_pairs():
            k = bisect.bisect_left(ends, b)
            label = "other"
            if k < len(steps) and steps[k].t_start <= b:
                tokens = sum(p["tokens"] for p in steps[k].prefills)
                label = "decode" if not steps[k].prefills else "cte%d" % next(
                    (bk for bk in buckets if bk >= tokens), buckets[-1])
            found.setdefault(label, []).append(b - a)
        total = sum(len(v) for v in found.values())
        modes = [{"mode": k, "gaps": len(v), "share_pct": 100.0 * len(v) / total,
                  "lo_ms": 1e3 * min(v), "median_ms": 1e3 * median(v), "hi_ms": 1e3 * max(v)}
                 for k, v in found.items()]
        return sorted(modes, key=lambda m: m["median_ms"])

    def decode_only_steps(self) -> list:
        """Steps that ran one token-generation dispatch and no prefill."""
        return [r for r in self.steps if r.decode is not None and not r.prefills]
