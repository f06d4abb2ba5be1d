"""Engine step: median of a step's wall time less its ``fetch`` phase (the
wait for the device's tokens), ``(t_end - t_start) - StepRecord.phases["fetch"]``,
over the steps that ran a token-generation dispatch and no prefill: the time
the engine keeps the device without work to wait for. ms. Nothing to read from
a program whose step records carry no phases."""

from benchmark.records import median


def read(run):
    v = median([
        (r.t_end - r.t_start) - r.phases["fetch"] for r in run.decode_only_steps()
        if "fetch" in (getattr(r, "phases", None) or {})
    ])
    return None if v is None else v * 1e3
