"""What the program itself wrote into a traced run's profiler trace, for the
per-layer readers that ``trace_reduce.TraceSummary`` has no field for: device
time per kernel, by the kernel's own name, and the device's idle time split by
the engine step's phases.

What the program writes (PR 26; looked at by hand on the v5e, ``PERF.md``
sections 5 and 6):

- host line of the thread that steps the engine (``python3``): one
  ``nxdi.step`` event per ``InferenceEngine.step()`` (``StepTraceAnnotation``;
  its ``step_num`` stat is ``StepRecord.step``) and inside it the phases
  ``nxdi.step.schedule``, ``.kv``, ``.pack``, ``.pad``, ``.enqueue``,
  ``.fetch``, ``.emit`` (``Telemetry.phase``), which never overlap;
- ``XLA Modules`` of a chip: the step programs execute as
  ``jit_token_generation_model_<bucket>__<process token>_<n>(<fingerprint>)``
  and ``jit_context_encoding_model_<bucket>__...``;
- ``XLA Ops`` of a chip: a Pallas kernel is the event whose HLO text starts
  ``%<kernel>.<n> = ... custom-call(``, where ``<kernel>`` is the ``name=`` of
  its ``pl.pallas_call`` and that the name of the public function that
  launches it (``paged_attention_decode``, ``flash_attention_prefill``, ...).
  The kernel's name is in the INSTRUCTION NAME, which is all this file reads.
  The ``jax.named_scope`` regions of the step program (``layers``,
  ``attn.core``, ``kv.write``, ...) are in neither the event's name nor its
  stats (``device_offset_ps``, ``device_duration_ps``): they are the stat
  ``tf_op`` of the event's METADATA record (one per distinct instruction,
  beside ``source``, ``hlo_category`` and ``bytes_accessed``), which
  ``jax.profiler.ProfileData`` does not hand out; the raw protobuf does
  (``chip_calls/pr26_traced_run.py look_metadata``).

A trace of a program without these (an older commit) has none of the names:
every function here then returns None, and the reader leaves its metric out.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import List, Optional, Sequence, Tuple

from benchmark.trace_reduce import (
    DEVICE_PLANE,
    HOST_PLANE,
    LINE_MODULES,
    LINE_OPS,
    MIN_GAP_NS,
    Planes,
    load_xplane,
    module_base,
    union_ns,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")  # where run.py's Tracer writes

PHASE_PREFIX = "nxdi.step."
TKG_MODULE = "jit_token_generation_model"
NOTE = "program_trace"


def newest_xplane(root: str = TRACE_ROOT) -> Optional[str]:
    """The run's own trace: the tracer empties its cell's directory before it
    starts, so the newest ``.xplane.pb`` under ``root`` is this run's."""
    found = glob.glob(os.path.join(root, "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def of(run) -> Optional[Planes]:
    """The planes of a traced run's own trace, loaded once per run (kept in
    ``run.notes``); None in a run that was not traced."""
    if run.trace is None:
        return None
    if NOTE not in run.notes:
        path = newest_xplane()
        run.notes[NOTE] = load_xplane(path) if path else None
    return run.notes[NOTE]


def _device_planes(planes: Planes) -> List[Tuple[int, str]]:
    return sorted((int(m.group(1)), n) for n in planes if (m := DEVICE_PLANE.match(n)))


def instruction_name(hlo_text: str) -> str:
    """``%paged_attention_decode.3 = bf16[...] custom-call(...)`` ->
    ``paged_attention_decode.3``."""
    return hlo_text.partition(" = ")[0].strip().lstrip("%")


def kernel_s_per_execution(
    planes: Planes, kernel: str, module_prefix: str = TKG_MODULE
) -> Optional[float]:
    """Device seconds of the ``XLA Ops`` events whose instruction is
    ``<kernel>`` or ``<kernel>.<n>``, first chip, that start inside an
    ``XLA Modules`` execution whose name starts with ``module_prefix``, over
    the number of those executions. None when there is no such execution or
    no such event."""
    devices = _device_planes(planes)
    if not devices:
        return None
    lines = planes[devices[0][1]]
    runs = sorted(
        (start, start + dur) for name, start, dur in lines.get(LINE_MODULES, [])
        if module_base(name).startswith(module_prefix)
    )
    if not runs:
        return None
    starts = [s for s, _ in runs]
    wanted = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    total, seen = 0, False
    for name, start, dur in lines.get(LINE_OPS, []):
        if not wanted.match(instruction_name(name)):
            continue
        k = bisect.bisect_right(starts, start) - 1
        if k >= 0 and start < runs[k][1]:
            total += dur
            seen = True
    return total / 1e9 / len(runs) if seen else None


def idle_gaps_ns(planes: Planes, span_prefix: str = "bench.") -> List[Tuple[int, int]]:
    """``(start, end)`` of every gap of at least ``MIN_GAP_NS`` between the
    busy intervals of the least busy chip: the idle time that
    ``TraceSummary.idle_gaps`` labels, over the same window (first device
    operation or harness span to the last)."""
    per_dev = {}
    for idx, name in _device_planes(planes):
        ops = [e for e in planes[name].get(LINE_OPS, []) if e[2] > 0]
        if ops:
            per_dev[idx] = ops
    if not per_dev:
        return []
    spans = [
        e for line in planes.get(HOST_PLANE, {}).values() for e in line
        if e[2] > 0 and e[0].startswith(span_prefix)
    ]
    every = [e for ops in per_dev.values() for e in ops] + spans
    t0, t1 = min(e[1] for e in every), max(e[1] + e[2] for e in every)
    merged = {i: union_ns((e[1], e[1] + e[2]) for e in ops) for i, ops in per_dev.items()}
    worst = min(merged, key=lambda i: merged[i][0])
    edges = [(t0, t0)] + merged[worst][1] + [(t1, t1)]
    return [
        (prev_end, next_start)
        for (_, prev_end), (next_start, _) in zip(edges, edges[1:])
        if next_start - prev_end >= MIN_GAP_NS
    ]


def phase_spans(planes: Planes) -> List[Sequence]:
    """The host events the engine step's phases wrote, ``[name, start, dur]``."""
    return [
        e for line in planes.get(HOST_PLANE, {}).values() for e in line
        if e[2] > 0 and e[0].startswith(PHASE_PREFIX)
    ]


def idle_by_phase_s(planes: Planes) -> dict:
    """``{phase or "no phase": idle seconds}``: the time of the gaps of
    :func:`idle_gaps_ns` that each phase of the step overlaps (phases never
    overlap one another), and what is left under none. Empty when the trace
    holds no phase span (the program does not write them) or no gap."""
    phases = sorted(phase_spans(planes), key=lambda e: e[1])
    gaps = idle_gaps_ns(planes)
    if not phases or not gaps:
        return {}
    starts = [e[1] for e in phases]
    out = {"no phase": 0.0}
    for start, end in gaps:
        covered = 0
        k = max(bisect.bisect_right(starts, start) - 1, 0)
        while k < len(phases) and phases[k][1] < end:
            name, p0, dur = phases[k]
            both = min(end, p0 + dur) - max(start, p0)
            if both > 0:
                label = name[len(PHASE_PREFIX):]
                out[label] = out.get(label, 0.0) + both / 1e9
                covered += both
            k += 1
        out["no phase"] += (end - start - covered) / 1e9
    return out


def idle_unattributed_pct(planes: Planes) -> Optional[float]:
    """Of the idle time in :func:`idle_gaps_ns`, the share (in %) that no
    ``nxdi.step.<phase>`` span overlaps. None when the trace holds no such
    span or no gap.

    ISSUE 26 asked for the share of the gaps whose MIDPOINT lies under no
    phase, the rule ``breakdown.idle_gaps`` labels by. A step's idle time is
    one gap of ~4.5 ms from the end of one execution to the start of the next,
    and its midpoint falls within a few tenths of a millisecond of the step's
    boundary: over four traced runs on the v5e that rule read 0.0, 3.8, 9.2
    and 21.1 % (``PERF.md``, PR 26). The overlap itself is what the name says,
    and it is steady."""
    by_phase = idle_by_phase_s(planes)
    idle = sum(by_phase.values())
    return 100.0 * by_phase["no phase"] / idle if idle else None
