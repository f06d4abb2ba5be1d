"""Scheduler: share of the window's token-generation steps into which the
scheduler also put a prefill, so that every running stream waited through it.
The token gaps fall into one mode per kind of step (decode only, or decode
plus a prefill of some bucket); this share says how much of the distribution
the prefill modes hold, and so which of them a gap percentile lies in. %."""


def read(run):
    decode = [r for r in run.steps if r.decode is not None]
    if not decode:
        return None
    return 100.0 * sum(1 for r in decode if r.prefills) / len(decode)
