"""Device: of the idle time that ``breakdown.idle_gaps`` labels (gaps of at
least 20 us on the least busy chip), the share that no ``nxdi.step.<phase>``
span of the program overlaps: idle time that no phase of the engine step
explains (the time between two steps, the harness's own, and what the step
does under no phase). %. Nothing to read where the trace holds no such span."""

from benchmark import program_trace


def read(run):
    planes = program_trace.of(run)
    return None if planes is None else program_trace.idle_unattributed_pct(planes)
