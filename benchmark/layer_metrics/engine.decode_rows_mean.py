"""Engine step: mean number of rows in a token-generation dispatch
(``len(StepRecord.decode.rows)``). count."""


def read(run):
    rows = [len(r.decode["rows"]) for r in run.steps if r.decode is not None]
    return sum(rows) / len(rows) if rows else None
