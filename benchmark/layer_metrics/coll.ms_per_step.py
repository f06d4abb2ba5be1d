"""Collectives: summed device time of the all-reduce / all-gather (and other
collective) operations inside one token-generation execution, first chip. ms.
Nothing to read on one chip."""


def read(run):
    if run.trace is None or run.trace.collective_s_per_tkg is None:
        return None
    return run.trace.collective_s_per_tkg * 1e3
