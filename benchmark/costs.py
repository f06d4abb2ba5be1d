"""The chip's peaks, and the least time a piece of work can take on them: the
yardstick's half of a roofline share. What the work IS (bytes, operations)
comes from the configuration's own cost model, ``cost_models/<name>.py``,
named in its file; the program's ``analysis/costs.py`` is not consulted.
"""

from __future__ import annotations

import json
import os
from typing import Dict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def peaks_of(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def least_s(work: Dict[str, float], chips: int, peaks: Dict[str, float]) -> Dict[str, float]:
    """Least seconds ``work`` (``bytes`` and ``flops`` of the whole model,
    split evenly over ``chips``) can take on one chip: bytes over the HBM peak
    or operations over the bf16 peak, whichever is larger."""
    by_bytes = work["bytes"] / chips / peaks["hbm_bytes_per_s"]
    by_flops = work["flops"] / chips / peaks["bf16_flops_per_s"]
    return {
        "by_bytes_s": by_bytes, "by_flops_s": by_flops,
        "least_s": max(by_bytes, by_flops), "bound": "bytes" if by_bytes >= by_flops else "flops",
    }
