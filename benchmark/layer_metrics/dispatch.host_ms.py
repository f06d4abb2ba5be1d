"""Program dispatch: median host time of the wrapper per token-generation
dispatch, ``StepRecord.phases["pad"] + phases["enqueue"]`` (bucket choice and
numpy padding; then host-to-device puts, the program call and the output
slice), over the steps that ran a token-generation dispatch and no prefill.
ms. Nothing to read from a program whose step records carry no phases."""

from benchmark.records import median


def read(run):
    v = median([
        r.phases["pad"] + r.phases["enqueue"] for r in run.decode_only_steps()
        if {"pad", "enqueue"} <= set(getattr(r, "phases", None) or {})
    ])
    return None if v is None else v * 1e3
