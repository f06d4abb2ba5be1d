"""Static program analysis over the AOT-compiled submodel zoo.

NxDI serves from a small, fixed set of AOT-compiled programs — which means
nearly every production failure mode is statically visible in the lowered
jaxpr/HLO before a single request is served: an undonated KV cache doubling
HBM, a sharding-policy typo inserting an extra all-gather per layer, a silent
fp32 upcast in a bf16 path, a weight baked into the graph as a constant, a
stray retrace mid-serving.

This package is the audit layer over that program set:

- :mod:`~nxdi_tpu.analysis.checkers` — the checker suite (donation audit,
  collective budget, dtype-drift lint, baked-constant lint, required kernel
  strategies), each returning :class:`Finding` records.
- :mod:`~nxdi_tpu.analysis.auditor` — :func:`audit_application` /
  :func:`audit_wrapper` orchestration + JSON reports.
- :mod:`~nxdi_tpu.analysis.budget` — expected collective counts derived from
  the config's ShardingPolicy.
- :mod:`~nxdi_tpu.analysis.costs` — the cost observatory: per-program
  FLOP/HBM CostSheets (XLA ``cost_analysis``/``memory_analysis``
  cross-checked against an analytic model), roofline classification on
  declared chip specs, the ``hbm_fit`` budget, and the registry attachment
  publishing the ``nxdi_program_mfu_pct`` family of gauges.
- :mod:`~nxdi_tpu.analysis.retrace` — the serve-time retrace guard
  (``TpuConfig.retrace_guard``).
- :mod:`~nxdi_tpu.analysis.source_lint` — stdlib pyflakes-lite (unused
  imports / undefined names) gating tier-1; mirrors the repo ``ruff.toml``.
- :mod:`~nxdi_tpu.analysis.concurrency` — the host-plane concurrency
  auditor: thread-entrypoint discovery, lock-discipline (``guarded_by``)
  enforcement, lock-order-cycle and blocking-under-lock detection over the
  serving plane's driver/HTTP/poller threads.

The program-audit surface (auditor/checkers/costs) imports jax at module
scope; the source-level surfaces (``source_lint``, ``concurrency``) are
stdlib-only. Attribute access is therefore lazy (PEP 562): importing
``nxdi_tpu.analysis`` — e.g. for the ``guarded_by`` marker used across the
serving plane — stays cheap, and the heavy modules load on first touch.

CLI: ``python -m nxdi_tpu.cli.lint`` (per-model JSON report, nonzero exit on
violations); ``--concurrency`` for the host-plane report.
"""

import importlib

# Concurrency markers are decorators applied at import time across the
# serving plane — eager and dependency-free by design.
from nxdi_tpu.analysis.concurrency import guarded_by, thread_entrypoint

_EXPORTS = {
    # auditor (imports jax)
    "AuditReport": "nxdi_tpu.analysis.auditor",
    "ProgramReport": "nxdi_tpu.analysis.auditor",
    "audit_application": "nxdi_tpu.analysis.auditor",
    "audit_wrapper": "nxdi_tpu.analysis.auditor",
    "check_cache_format_agreement": "nxdi_tpu.analysis.auditor",
    "check_param_formats": "nxdi_tpu.analysis.auditor",
    "collective_summary": "nxdi_tpu.analysis.auditor",
    # budget
    "expected_collective_budget": "nxdi_tpu.analysis.budget",
    # costs (imports jax)
    "CHIP_SPECS": "nxdi_tpu.analysis.costs",
    "ChipSpec": "nxdi_tpu.analysis.costs",
    "CostSheet": "nxdi_tpu.analysis.costs",
    "attach_cost_gauges": "nxdi_tpu.analysis.costs",
    "cost_sheets": "nxdi_tpu.analysis.costs",
    "cost_summary": "nxdi_tpu.analysis.costs",
    "resolve_chip": "nxdi_tpu.analysis.costs",
    # checkers (imports jax)
    "CHECKERS": "nxdi_tpu.analysis.checkers",
    "DEFAULT_CONST_THRESHOLD_BYTES": "nxdi_tpu.analysis.checkers",
    "Finding": "nxdi_tpu.analysis.checkers",
    "ProgramArtifacts": "nxdi_tpu.analysis.checkers",
    "missing_required_strategies": "nxdi_tpu.analysis.checkers",
    "required_strategy_error": "nxdi_tpu.analysis.checkers",
    # retrace guard
    "RetraceAfterServingError": "nxdi_tpu.analysis.retrace",
    "RetraceGuard": "nxdi_tpu.analysis.retrace",
    # concurrency auditor (stdlib-only)
    "ConcurrencyFinding": "nxdi_tpu.analysis.concurrency",
    "ConcurrencyReport": "nxdi_tpu.analysis.concurrency",
    "analyze_paths": "nxdi_tpu.analysis.concurrency",
    "analyze_sources": "nxdi_tpu.analysis.concurrency",
}

__all__ = sorted(set(_EXPORTS) | {"guarded_by", "thread_entrypoint"})


def __getattr__(name):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value  # cache: next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
