"""Scheduler: median time one engine step spent deciding what to run
(``StepRecord.phases["schedule"]``: ``schedule_prefills``, ``decodable``, the
choice of decode program, ``scheduler.publish``), over the window's steps that
ran a token-generation dispatch. ms. Nothing to read from a program whose
step records carry no phases."""

from benchmark.records import median


def read(run):
    v = median([
        r.phases["schedule"] for r in run.steps
        if r.decode is not None and "schedule" in (getattr(r, "phases", None) or {})
    ])
    return None if v is None else v * 1e3
