"""Static program auditor over every AOT-lowered submodel program.

Entry points:

- :func:`audit_application` — build (no weights needed) and audit every
  ``(submodel, bucket[, steps])`` program of an application; returns an
  :class:`AuditReport` (JSON-able, one :class:`ProgramReport` per program).
- :func:`audit_wrapper` — the same for a single ModelWrapper.
- :func:`collective_summary` — cheap per-program collective counts from the
  executables a *loaded* app already holds (no retracing; what the bench
  probes print next to their latency lines).

Auditing traces/lowers with abstract args exactly like ``aot_compile`` —
weights never load, so the auditor runs anywhere the compiler runs (the lint
CLI audits TPU-shaped programs from a CPU box via the same path tests use).
"""

from __future__ import annotations

import json
import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.tree_util as jtu

from nxdi_tpu.analysis import hlo as hlo_views
from nxdi_tpu.analysis.checkers import (
    CHECKERS,
    DEFAULT_CONST_THRESHOLD_BYTES,
    Finding,
    ProgramArtifacts,
)

logger = logging.getLogger("nxdi_tpu")


def _kept_args(lowered):
    """Flat indices of the args the lowering KEPT (unused args are pruned
    from the HLO signature) — a private field of ``Lowered``, read in this
    one place."""
    return tuple(sorted(lowered._lowering.compile_args["kept_var_idx"]))


def _donated_flags(lowered):
    """Per-flat-arg donation flags from ``Lowered.args_info``."""
    flat = jtu.tree_leaves(
        lowered.args_info, is_leaf=lambda x: hasattr(x, "donated")
    )
    return tuple(bool(a.donated) for a in flat)


def _cache_input_formats(compiled):
    """The resolved AUTO layouts of an executable's cache input subtree
    (arg 1 of ``(params, cache, batch)``)."""
    return compiled.input_formats[0][1]


def _param_input_formats(compiled):
    """The layouts an executable takes its params in (arg 0)."""
    return compiled.input_formats[0][0]


#: the passes over the whole program set (not per-program checkers): valid
#: names in a ``checkers`` selection beside ``CHECKERS``
CROSS_PROGRAM = ("cache_format", "param_format")


def _key_str(key) -> str:
    if isinstance(key, tuple):
        return "k" + ",".join(str(k) for k in key)
    return str(key)


def _leaf_paths(tree) -> List[str]:
    flat, _ = jtu.tree_flatten_with_path(tree)
    return [jtu.keystr(path).lstrip(".") or str(i) for i, (path, _) in enumerate(flat)]


@dataclass
class ProgramReport:
    tag: str
    key: Any
    label: str
    collectives: Dict[str, int] = field(default_factory=dict)
    budget: Dict[str, int] = field(default_factory=dict)
    cache_inputs: int = 0
    donated_cache_inputs: int = 0
    strategies: List[str] = field(default_factory=list)
    largest_const_bytes: int = 0
    findings: List[Finding] = field(default_factory=list)
    # stringified per-leaf cache input formats (AUTO layout resolution) for
    # the cross-program agreement check; None when the backend has no view
    cache_formats: Optional[tuple] = None
    # the same for the params (check_param_formats), and the instructions
    # that move a weight into another layout (analysis/hlo.py param_moves)
    param_formats: Optional[tuple] = None
    param_moves: List[str] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "submodel": self.tag,
            "program": self.label,
            "key": _key_str(self.key),
            "collectives": self.collectives,
            "collective_budget": self.budget,
            "cache_inputs": self.cache_inputs,
            "donated_cache_inputs": self.donated_cache_inputs,
            "attention_strategies": self.strategies,
            "largest_const_bytes": self.largest_const_bytes,
            "cache_formats": (
                list(self.cache_formats) if self.cache_formats else None
            ),
            "param_moves": list(self.param_moves),
            "findings": [f.to_dict() for f in self.findings],
        }


@dataclass
class AuditReport:
    programs: List[ProgramReport] = field(default_factory=list)
    retrace: Optional[dict] = None

    @property
    def findings(self) -> List[Finding]:
        return [f for p in self.programs for f in p.findings]

    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    def ok(self, fail_on: str = "error") -> bool:
        if fail_on == "warning":
            return not self.findings
        return not self.errors()

    def to_dict(self, fail_on: str = "error") -> dict:
        d = {
            "ok": self.ok(fail_on=fail_on),
            "programs": [p.to_dict() for p in self.programs],
            "n_findings": len(self.findings),
        }
        if self.retrace is not None:
            d["retrace_guard"] = self.retrace
        return d

    def to_json(self, indent: int = 2, fail_on: str = "error") -> str:
        return json.dumps(self.to_dict(fail_on=fail_on), indent=indent)

    def collective_lines(self) -> Dict[str, Dict[str, int]]:
        """{program label: nonzero collective counts} — the probes' summary."""
        return {
            p.label: {op: n for op, n in p.collectives.items() if n}
            for p in self.programs
        }


def _max_const_bytes(closed_jaxpr) -> int:
    import numpy as np

    best = 0
    try:
        for c in closed_jaxpr.consts:
            best = max(best, int(np.asarray(c).nbytes))
    except Exception:
        pass
    return best


def audit_wrapper(
    wrapper,
    params_struct,
    cache_struct,
    config=None,
    checkers: Optional[Sequence[str]] = None,
    const_threshold: int = DEFAULT_CONST_THRESHOLD_BYTES,
    reuse_compiled: bool = True,
    shared: Optional[dict] = None,
) -> List[ProgramReport]:
    """Audit every compiled program of one ModelWrapper.

    ``params_struct`` / ``cache_struct`` are the abstract pytrees the app's
    ``aot_compile`` uses (ShapeDtypeStructs, shardings attached here).
    ``shared`` is the one-dict-per-audit state letting checkers run their
    program-independent passes once (audit_application threads a single
    dict through every wrapper).
    """
    from nxdi_tpu.ops import attention_select

    if shared is None:
        shared = {}

    config = config or wrapper.config
    # the CROSS_PROGRAM passes audit_application runs are valid selections
    # here, just not per-program checkers. Anything else unknown still
    # surfaces as a finding (a typo'd name must not read as "checker ran
    # clean").
    requested = list(checkers) if checkers is not None else list(CHECKERS)
    names = [n for n in requested if n in CHECKERS]
    unknown = [n for n in requested if n not in CHECKERS and n not in CROSS_PROGRAM]

    def attach(struct, shardings):
        return jtu.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            struct, shardings,
        )

    ps = attach(params_struct, wrapper._param_shardings)
    cs = attach(cache_struct, wrapper._cache_shardings)
    n_param_leaves = len(jtu.tree_leaves(ps))
    param_paths = _leaf_paths(ps)
    param_dims = [tuple(a.shape) for a in jtu.tree_leaves(ps)]
    cache_paths = tuple(_leaf_paths(cs))
    from nxdi_tpu.analysis.costs import tree_bytes

    param_bytes = tree_bytes(ps)
    cache_bytes = tree_bytes(cs)

    reports = []
    for key, prog in wrapper._programs.items():
        label = getattr(prog, "label", f"{wrapper.tag}[{_key_str(key)}]")
        report = ProgramReport(tag=wrapper.tag, key=key, label=label)
        reports.append(report)
        for n in unknown:
            report.findings.append(Finding(
                "auditor", "warning", wrapper.tag, label,
                f"unknown checker {n!r} requested; known: "
                f"{sorted(CHECKERS) + list(CROSS_PROGRAM)}",
            ))
        try:
            example = wrapper._example_for_key(key)
            with jax.set_mesh(wrapper._mesh):
                attention_select._STRATEGY_TRACE.clear()
                traced = prog.jitted.trace(ps, cs, example)
                lowered = traced.lower()
                strategies = tuple(attention_select._STRATEGY_TRACE) or tuple(
                    prog.attention_strategies
                )
                if reuse_compiled and prog._compiled is not None:
                    compiled = prog._compiled
                else:
                    compiled = lowered.compile()
        except Exception as e:  # an unauditable program is itself a finding
            report.findings.append(Finding(
                "auditor", "error", wrapper.tag, label,
                f"program could not be traced/lowered for audit: {type(e).__name__}: {e}",
            ))
            continue

        art = ProgramArtifacts(
            wrapper=wrapper,
            tag=wrapper.tag,
            key=key,
            label=label,
            config=config,
            arch=wrapper.arch,
            jaxpr=traced.jaxpr,
            stablehlo=lowered.as_text(),
            hlo=compiled.as_text(),
            strategies=strategies,
            n_param_leaves=n_param_leaves,
            cache_paths=cache_paths,
            kept_args=_kept_args(lowered),
            donated_flags=_donated_flags(lowered),
            const_threshold=const_threshold,
            compiled=compiled,
            param_bytes=param_bytes,
            cache_bytes=cache_bytes,
            params_struct=ps,
            shared=shared,
        )
        for name in names:
            try:
                report.findings.extend(CHECKERS[name](art))
            except Exception as e:
                report.findings.append(Finding(
                    "auditor", "warning", wrapper.tag, label,
                    f"checker {name!r} crashed: {type(e).__name__}: {e}",
                ))

        report.collectives = art.collectives or (
            hlo_views.collective_counts(art.hlo) if art.hlo else {}
        )
        from nxdi_tpu.analysis.budget import expected_collective_budget

        report.budget = expected_collective_budget(
            config.tpu_config, wrapper.arch, wrapper
        )[0]
        report.strategies = list(strategies)
        report.cache_inputs = len(cache_paths)
        report.donated_cache_inputs = min(
            len(hlo_views.aliased_arg_positions(art.stablehlo)),
            len(cache_paths),
        )
        report.largest_const_bytes = _max_const_bytes(traced.jaxpr)
        # compared across programs by check_cache_format_agreement
        report.cache_formats = tuple(
            str(f) for f in jtu.tree_leaves(_cache_input_formats(compiled))
        )
        # read by check_param_formats
        report.param_formats = tuple(
            str(f.layout) for f in jtu.tree_leaves(_param_input_formats(compiled))
        )
        if wrapper.attend_to_cache and not wrapper.prefill_to_cache:
            report.param_moves = [
                f"{m.what} {m.instruction} {m.shape}: "
                + " or ".join(param_paths[i] for i in m.leaves)
                for m in hlo_views.param_moves(art.hlo or "", param_dims, art.kept_args)
            ]
    return reports


def check_cache_format_agreement(
    reports: Sequence[ProgramReport],
) -> List[Finding]:
    """Every program of one app donates and returns THE SAME cache pytree,
    so they must all resolve their AUTO memory layouts to the same per-leaf
    formats — a prefill/decode pair that disagrees pays a full-cache
    relayout (``device_put`` per leaf, ~GBs) at EVERY phase transition
    (`_AutoLayoutProgram.__call__` moves the cache whenever the incoming
    format differs from the program's preference). Findings are attached to
    the later program, naming the agreeing reference."""
    ref = None
    findings: List[Finding] = []
    for report in reports:
        if report.cache_formats is None:
            continue
        if ref is None:
            ref = report
            continue
        if report.cache_formats != ref.cache_formats:
            diff = [
                i for i, (a, b) in enumerate(
                    zip(report.cache_formats, ref.cache_formats)
                ) if a != b
            ] or ["count"]
            f = Finding(
                "cache_format", "error", report.tag, report.label,
                f"AUTO cache layouts disagree across the program set: "
                f"{report.label} resolved {list(report.cache_formats)} but "
                f"{ref.label} resolved {list(ref.cache_formats)} (differing "
                f"leaves: {diff}) — every {ref.tag} -> {report.tag} phase "
                "transition pays a full-cache relayout at dispatch time",
            )
            report.findings.append(f)
            findings.append(f)
    return findings


def check_param_formats(reports: Sequence[ProgramReport]) -> List[Finding]:
    """Every program of one app takes the params in the same per-leaf
    formats (the app holds ONE copy of the weights, laid out once at load:
    runtime/param_layout.py), and no decode-step program moves a weight into
    another layout: a copy of a leaf, or of the layer scan's slice of one,
    inside the step is that copy on every token. Findings on the later
    program, naming the reference one."""
    ref = None
    findings: List[Finding] = []
    for report in reports:
        for move in report.param_moves:
            f = Finding(
                "param_format", "error", report.tag, report.label,
                f"{report.label} moves a weight into another layout on every "
                f"step: {move} — lay the params out once at load in the format "
                "this program reads them in (runtime/param_layout.py)",
            )
            report.findings.append(f)
            findings.append(f)
        if report.param_formats is None:
            continue
        if ref is None:
            ref = report
            continue
        if report.param_formats != ref.param_formats:
            diff = [
                i for i, (a, b) in enumerate(zip(report.param_formats, ref.param_formats))
                if a != b
            ] or ["count"]
            f = Finding(
                "param_format", "error", report.tag, report.label,
                f"the params' layouts disagree across the program set: "
                f"{report.label} takes leaves {diff} as "
                f"{[report.param_formats[i] for i in diff if isinstance(i, int)]} but "
                f"{ref.label} as {[ref.param_formats[i] for i in diff if isinstance(i, int)]}"
                " — one copy of the weights cannot serve both without a relayout "
                "on every call",
            )
            report.findings.append(f)
            findings.append(f)
    return findings


def audit_application(
    app,
    submodels: Optional[Sequence[str]] = None,
    checkers: Optional[Sequence[str]] = None,
    const_threshold: int = DEFAULT_CONST_THRESHOLD_BYTES,
    reuse_compiled: bool = True,
) -> AuditReport:
    """Audit every submodel program of an application (weights not required)."""
    app._build_wrappers()
    params_struct = app.build_params_struct()
    cache_struct = app._cache_struct()
    report = AuditReport()
    shared: dict = {}  # one per audit: checkers dedupe cross-program passes
    for tag, wrapper in app.models.items():
        if submodels is not None and tag not in submodels:
            continue
        try:
            report.programs.extend(audit_wrapper(
                wrapper, params_struct, cache_struct, config=app.config,
                checkers=checkers, const_threshold=const_threshold,
                reuse_compiled=reuse_compiled, shared=shared,
            ))
        except Exception as e:
            report.programs.append(ProgramReport(
                tag=tag, key=None, label=tag,
                findings=[Finding(
                    "auditor", "warning", tag, tag,
                    f"wrapper could not be audited: {type(e).__name__}: {e}",
                )],
            ))
    # cross-program invariant: every program must resolve the shared cache
    # pytree to the SAME AUTO layout, or phase transitions pay a relayout
    # (not a per-program checker — it needs the whole program set)
    if checkers is None or "cache_format" in checkers:
        check_cache_format_agreement(report.programs)
    if checkers is None or "param_format" in checkers:
        check_param_formats(report.programs)
    guard = getattr(app, "retrace_guard", None)
    if guard is not None:
        report.retrace = guard.to_dict()
        for msg in guard.violations:
            report.programs.append(ProgramReport(
                tag="<runtime>", key=None, label="<retrace-guard>",
                findings=[Finding(
                    "retrace", "error", "<runtime>", "<retrace-guard>", msg,
                )],
            ))
    return report


def collective_summary(app) -> Dict[str, Dict[str, int]]:
    """Per-program nonzero collective counts from the executables a LOADED
    app already holds — zero retracing/recompilation, safe on the hot path."""
    out: Dict[str, Dict[str, int]] = {}
    for tag, wrapper in getattr(app, "models", {}).items():
        for key, prog in getattr(wrapper, "_programs", {}).items():
            compiled = getattr(prog, "_compiled", None)
            if compiled is None:
                continue
            text = compiled.as_text()
            counts = hlo_views.collective_counts(text)
            label = getattr(prog, "label", f"{tag}[{_key_str(key)}]")
            out[label] = {op: n for op, n in counts.items() if n}
    return out
