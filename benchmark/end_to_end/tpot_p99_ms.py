"""99th percentile of the gaps between consecutive output tokens, over every
gap of every stream that lies inside the window (streams still running at its
close included; the ramp before it and the drain after it left out), as the
streaming callback saw the tokens arrive. A stall behind another request's
prefill or a preemption is a long gap of every stream that waited. ms."""

from benchmark.records import percentile


def read(run):
    v = percentile(run.token_gaps(), 99)
    return None if v is None else v * 1e3
