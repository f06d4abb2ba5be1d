"""KV manager: recompute preemptions inside the window, summed over the step
records (``StepRecord.preempted``). count."""


def read(run):
    return float(sum(len(r.preempted) for r in run.steps))
