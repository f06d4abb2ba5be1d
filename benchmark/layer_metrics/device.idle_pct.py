"""Device: 1 - (union of the intervals in which an operation ran) / (traced
window), on the least busy chip. %."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct_worst
