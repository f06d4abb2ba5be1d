"""Engine step: share of the steps that ran a token-generation dispatch and no
prefill whose dispatch went out BEFORE the previous step's tokens were
collected (``StepRecord.chained``: the step's input ids never left the device,
and its scheduling, packing and puts ran beside the previous program). %.
Nothing to read from a program whose step records carry no such field."""


def read(run):
    steps = [r for r in run.decode_only_steps() if hasattr(r, "chained")]
    if not steps:
        return None
    return 100.0 * sum(1 for r in steps if r.chained) / len(steps)
