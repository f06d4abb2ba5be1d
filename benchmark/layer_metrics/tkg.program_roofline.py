"""Model step programs: the least time the chip could take for the median
traced decode step over ``tkg.device_ms``. The step's bytes and operations
come from the configuration's cost model (``cost_models/<name>.py``, given the
step's rows and the live KV of those rows), the least time from
``benchmark/costs.py`` and ``benchmark/peaks.json``. %."""

from benchmark import cells, costs
from benchmark.records import median


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.tkg_device_s()
    live = median(run.notes.get("traced_live_kv_tokens", []))
    rows = median(run.notes.get("traced_rows", []))
    if not device_s or live is None or rows is None:
        return None
    tkg_step = cells.load_plugin("cost_model", run.config["benchmark"]["cost_model"])
    least = costs.least_s(tkg_step(run.config, rows, live), run.tp, costs.peaks_of(run.device_kind))
    return 100.0 * least["least_s"] / device_s
