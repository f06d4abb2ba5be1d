"""Kernels: the least time the chip could take for the ``paged_attention_decode``
launches of the median traced decode step (the configuration's cost model's
``paged_decode_kernel``: the live key and value rows once, no block or lane
padding, the queries in and the result out, and the two dots;
``benchmark/costs.py`` and ``peaks.json``) over the kernel's device time per
token-generation execution (``kernel.paged_decode_ms``). %. Nothing to read
where the trace has no such kernel or the cost model no such function."""

from benchmark import cells, costs, program_trace
from benchmark.records import median

KERNEL = "paged_attention_decode"


def read(run):
    planes = program_trace.of(run)
    if planes is None:
        return None
    kernel_s = program_trace.kernel_s_per_execution(planes, KERNEL)
    live = median(run.notes.get("traced_live_kv_tokens", []))
    rows = median(run.notes.get("traced_rows", []))
    work = cells.load_plugin("cost_model", run.config["benchmark"]["cost_model"], "paged_decode_kernel")
    if not kernel_s or live is None or rows is None or work is None:
        return None
    least = costs.least_s(work(run.config, rows, live), run.tp, costs.peaks_of(run.device_kind))
    return 100.0 * least["least_s"] / kernel_s
