"""Engine step: median wall time of one ``InferenceEngine.step()`` over the
steps that ran a token-generation dispatch and no prefill
(``StepRecord.t_end - t_start``). ms."""

from benchmark.records import median


def read(run):
    v = median([r.t_end - r.t_start for r in run.decode_only_steps()])
    return None if v is None else v * 1e3
