"""Model step programs, window layers: device time of the ``attention``-region
ops (``nxdi_tpu/telemetry/regions.py``: projections, norms, rope, the cache's
read and write, the core and the output) whose op_name lies under the window
layers' container scope ``layers.window`` (their layer scans and the ring
rows' commit after the walk) in one execution of the token-generation module:
the SELF time of those ops inside the module's executions in the traced window
(first chip), over the number of executions (``program_regions.py``'s rule).
ms. Nothing to read where the trace's ops carry no scope, or where the model
has no window layers."""

import bisect
import sys

from benchmark import program_regions, program_trace
from benchmark.trace_reduce import module_base, self_ns

NOTE = "tkg.window_attention_ms"
CONTAINER = "layers.window"


def window_attention_ms(dev):
    """The reading from one chip's plane (``program_regions.read_device``)."""
    from nxdi_tpu.telemetry import regions

    runs = sorted((s, s + d) for mid, s, d in dev.modules
                  if module_base(dev.names.get(mid, "")).startswith("jit_" + program_regions.TKG))
    if not runs:
        return None
    starts = [s for s, _ in runs]
    ours, total = {}, None
    for (mid, start, _), own in zip(dev.ops, self_ns(dev.ops)):
        k = bisect.bisect_right(starts, start) - 1
        if k < 0 or start >= runs[k][1]:
            continue
        if mid not in ours:
            op = dev.tf_op.get(mid) or ""
            ours[mid] = CONTAINER in op.split("/") and regions.region_of(op) == regions.ATTENTION
        if ours[mid]:
            total = (total or 0) + own
    return None if total is None else total / len(runs) / 1e9


def read(run):
    if run.trace is None:
        return None
    if NOTE not in run.notes:
        run.notes[NOTE] = None
        path = program_trace.newest_xplane()
        try:
            import nxdi_tpu.telemetry.regions  # noqa: F401  (a program without the region table)
        except ImportError:
            path = None
        if path:
            try:
                dev = program_regions.read_device(path)
                run.notes[NOTE] = window_attention_ms(dev) if dev is not None else None
            except (ValueError, IndexError, UnicodeError) as e:  # a trace this reader cannot parse
                print(f"window attention not read: {e!r}", file=sys.stderr)
    return run.notes[NOTE]
