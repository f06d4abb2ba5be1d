"""Profiler trace -> the numbers the traced run reports.

Two stages. :func:`load_xplane` turns the ``.xplane.pb`` the JAX profiler
writes into plain data, ``{plane: {line: [[name, start_ns, duration_ns], ...]}}``
(``jax.profiler.ProfileData``, nothing but JAX). :func:`reduce_trace` turns
that into a :class:`TraceSummary`. The second stage is what the CPU tests hold
against a small trace recorded on the v5e (``tests/benchmark/``).

What a v5e trace looks like (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per program
execution (``jit_<name>(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per HLO operation that ran, named by its whole HLO text (``%copy.65 =
bf16[...] copy(...)``); a ``while`` is an event that spans the events of its
body, which come once per iteration under the same name. The host is ``/host:CPU`` with one line per
thread, ``TraceAnnotation`` spans on the thread that made them. All planes
share one clock.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.records import median

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
LINE_MODULES = "XLA Modules"
LINE_OPS = "XLA Ops"
COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
#: idle gaps shorter than this are the device's own launch cadence, not a wait
MIN_GAP_NS = 20_000

Planes = Dict[str, Dict[str, List[Sequence]]]


def load_xplane(path: str) -> Planes:
    from jax.profiler import ProfileData

    planes: Planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                [e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events
            )
    return planes


OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_label(hlo_text: str) -> str:
    """``%copy.65 = bf16[36,...]{...} copy(bf16[...] %x)`` -> ``copy.65 copy``:
    the instruction's name and its opcode, without shapes and operands."""
    head, eq, rest = hlo_text.partition(" = ")
    m = OPCODE.search(" " + rest) if eq else None
    name = head.strip().lstrip("%")[:80]
    return f"{name} {m.group(1)}" if m else name


def self_ns(events: Sequence[Sequence]) -> List[int]:
    """Each event's duration minus that of the events nested directly inside
    it (a ``while`` minus its body), in the order given."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    inner = [0] * len(events)
    stack: List[int] = []
    for i in order:
        start, dur = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            inner[stack[-1]] += dur
        stack.append(i)
    return [max(0, e[2] - inner[i]) for i, e in enumerate(events)]


def module_base(name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def union_ns(intervals: Iterable[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total length and merged list of ``(start, end)`` intervals."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the chips used
    idle_pct_worst: float  # 1 - busy/window on the least busy chip, in %
    devices: int
    #: {program label: [seconds of each execution on the first chip]}
    module_s: Dict[str, List[float]] = field(default_factory=dict)
    #: collective seconds inside one token-generation execution, first chip
    collective_s_per_tkg: Optional[float] = None
    device_ops: List[list] = field(default_factory=list)  # [[op, self seconds]] top 10
    idle_gaps: List[list] = field(default_factory=list)  # [[host label, seconds]] top 10

    def tkg_device_s(self) -> Optional[float]:
        runs = [s for label, v in self.module_s.items()
                if label.startswith("token_generation_model") for s in v]
        return median(runs)


class _HostIndex:
    """What the host was doing at an instant: the harness's own span that
    covers it, then the innermost other host event that does (the covering
    event that started last; spans of one thread nest)."""

    SCAN = 4000  # events looked at before the instant; spans nest shallowly

    def __init__(self, events: List[Sequence], prefix: str):
        self.events = sorted(events, key=lambda e: e[1])
        self.starts = [e[1] for e in self.events]
        self.prefix = prefix

    def label(self, t_ns: int) -> str:
        outer = inner = None
        k = bisect.bisect_right(self.starts, t_ns)
        for name, start, dur in reversed(self.events[max(0, k - self.SCAN):k]):
            if t_ns >= start + dur:
                continue
            if name.startswith(self.prefix):
                outer = name
                break
            if inner is None:
                inner = name
        if outer and inner:
            return f"{outer}>{inner}"
        return outer or inner or "no host span"


def reduce_trace(
    planes: Planes,
    module_labels: Optional[Dict[str, str]] = None,
    span_prefix: str = "bench.",
) -> Optional[TraceSummary]:
    """None when no operation ran on a device in the trace."""
    module_labels = module_labels or {}
    devices = sorted(
        (int(m.group(1)), name) for name in planes if (m := DEVICE_PLANE.match(name))
    )
    per_dev_ops = {
        idx: [e for e in planes[name].get(LINE_OPS, []) if e[2] > 0] for idx, name in devices
    }
    per_dev_ops = {i: ops for i, ops in per_dev_ops.items() if ops}
    if not per_dev_ops:
        return None
    host_events = [
        e for line in planes.get(HOST_PLANE, {}).values() for e in line if e[2] > 0
    ]
    bench_spans = [e for e in host_events if e[0].startswith(span_prefix)]
    starts = [e[1] for ops in per_dev_ops.values() for e in ops] + [e[1] for e in bench_spans]
    ends = [e[1] + e[2] for ops in per_dev_ops.values() for e in ops] + [
        e[1] + e[2] for e in bench_spans
    ]
    t0, t1 = min(starts), max(ends)
    window_ns = t1 - t0

    busy = {}
    merged_by_dev = {}
    for idx, ops in per_dev_ops.items():
        busy[idx], merged_by_dev[idx] = union_ns((e[1], e[1] + e[2]) for e in ops)
    first = min(per_dev_ops)

    module_s: Dict[str, List[float]] = {}
    tkg_spans: List[Tuple[int, int]] = []
    first_plane = dict(devices)[first]
    for name, start, dur in planes[first_plane].get(LINE_MODULES, []):
        base = module_base(name)
        label = module_labels.get(base, base)
        module_s.setdefault(label, []).append(dur / 1e9)
        if label.startswith("token_generation_model"):
            tkg_spans.append((start, start + dur))

    coll = None
    if tkg_spans:
        tkg_spans.sort()
        bounds = [s for s, _ in tkg_spans]
        total = 0
        seen = False
        for name, start, dur in per_dev_ops[first]:
            if not COLLECTIVE.search(name.partition(" = ")[0]):
                continue
            seen = True
            k = bisect.bisect_right(bounds, start) - 1
            if k >= 0 and start < tkg_spans[k][1]:
                total += dur
        if seen:
            coll = total / 1e9 / len(tkg_spans)

    by_op: Dict[str, int] = {}
    for (name, _, _), own in zip(per_dev_ops[first], self_ns(per_dev_ops[first])):
        label = op_label(name)
        by_op[label] = by_op.get(label, 0) + own
    device_ops = [
        [name, ns / 1e9] for name, ns in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    ]

    # idle gaps of the least busy chip, by what the host was doing meanwhile
    worst = min(busy, key=busy.get)
    gaps: Dict[str, int] = {}
    host = _HostIndex(host_events, span_prefix)
    edges = [(t0, t0)] + merged_by_dev[worst] + [(t1, t1)]
    for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
        gap = next_start - prev_end
        if gap >= MIN_GAP_NS:
            label = host.label(prev_end + gap // 2)
            gaps[label] = gaps.get(label, 0) + gap
    idle_gaps = [
        [name, ns / 1e9] for name, ns in sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    ]

    return TraceSummary(
        window_s=window_ns / 1e9,
        busy_s=sum(busy.values()) / len(busy) / 1e9,
        idle_pct_worst=100.0 * (1.0 - busy[worst] / window_ns),
        devices=len(busy),
        module_s=module_s,
        collective_s_per_tkg=coll,
        device_ops=device_ops,
        idle_gaps=idle_gaps,
    )
