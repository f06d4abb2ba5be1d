"""Per-program cost observatory: FLOP/HBM model + roofline for every program.

NxDI serves from a small fixed set of AOT-compiled ``(submodel, bucket[,
steps])`` programs, so each one's cost is a *static, per-program* quantity —
computable before a single request is served and joinable against the
measured dispatch latencies the telemetry registry already records. This
module is that account:

- :func:`cost_sheets` — one :class:`CostSheet` per compiled program:
  XLA's own counters (``compiled.cost_analysis()`` FLOPs / bytes accessed,
  ``compiled.memory_analysis()`` argument/output/temp HBM) cross-checked
  against an **analytic model** derived from the config/arch (weight bytes
  by dtype, KV bytes per bucket window, matmul + attention FLOPs —
  scan-aware like the collective-budget checker: counts follow the math,
  not the HLO text). When a backend returns ``None``/partial analyses
  (CPU, older jaxlib, pallas custom calls) the sheet degrades to the
  analytic numbers and is tagged ``source="analytic"`` — never an error.
- Roofline classification per declared :class:`ChipSpec` (default v5e):
  ``t_compute = flops/peak_flops``, ``t_hbm = bytes/peak_bw``; the floor is
  their max and ``bound`` says which ceiling the program sits under.
- An HBM-fit account (weights + max-live KV + XLA temp vs per-chip HBM)
  shared with the auditor's ``hbm_fit`` checker (analysis/checkers.py).
- :func:`attach_cost_gauges` — the runtime join: at every telemetry export
  the measured mean dispatch latency per (submodel, bucket, steps) is
  divided by the program's CostSheet to publish
  ``nxdi_program_mfu_pct`` / ``nxdi_program_hbm_bw_pct`` /
  ``nxdi_roofline_gap_ratio`` gauges, and the whole sheet table rides the
  JSON snapshot as ``_cost_sheets``.

Canonical-number policy: the roofline/MFU math reads the ANALYTIC flops and
bytes. XLA's counters are recorded alongside (``xla_flops``/``xla_bytes``)
and cross-checked (>2x divergence sets ``mismatch`` and logs a warning),
but they are not the trajectory quantity: XLA reports the partitioned
module's textual totals, which miss pallas custom-call FLOPs entirely and
count causally-masked attention at full density — so they move when the
lowering strategy moves. The analytic model is what ``bench.py``'s
``cte_mfu_pct``/``mfu_pct``/``hbm_roofline_pct`` trajectory has always
meant, and using it for the serving gauges too means BENCH_*.json and the
Prometheus export can never disagree.

Analytic numbers are GLOBAL then divided by the mesh world (tp*pp) for the
per-chip roofline; XLA numbers come from the partitioned per-device module
and are per-chip already. CLI: ``python -m nxdi_tpu.cli.costs``.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

logger = logging.getLogger("nxdi_tpu")


# ---------------------------------------------------------------------------
# chip specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ChipSpec:
    """Declared per-chip peaks the roofline is computed against (datasheet
    numbers; the bf16 peak — the serving dtype — not the int8 TOPS line)."""

    name: str
    bf16_tflops: float  # peak dense bf16 TFLOP/s
    hbm_gbs: float      # peak HBM bandwidth, GB/s (1e9)
    hbm_gib: float      # HBM capacity per chip, GiB (2**30)
    # the ``device_kind`` strings an attached device of this part reports
    device_kinds: Tuple[str, ...] = ()

    @property
    def flops_per_s(self) -> float:
        return self.bf16_tflops * 1e12

    @property
    def bytes_per_s(self) -> float:
        return self.hbm_gbs * 1e9

    @property
    def hbm_bytes(self) -> float:
        return self.hbm_gib * 2.0 ** 30

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "bf16_tflops": self.bf16_tflops,
            "hbm_gbs": self.hbm_gbs,
            "hbm_gib": self.hbm_gib,
        }


#: THE peaks table: per-chip bf16 TFLOP/s, HBM GB/s and HBM GiB, looked up by
#: declared name (``TpuConfig(chip=...)``) or by the ``device_kind`` an
#: attached device reports. Source of the peaks: Google Cloud TPU
#: documentation, system-architecture pages "TPU v4" / "TPU v5e" / "TPU v5p" /
#: "TPU v6e" (per-chip figures); the device_kind strings are the ones
#: jax/_src/pallas/mosaic/tpu_info.py matches on.
CHIP_SPECS: Dict[str, ChipSpec] = {
    "v4": ChipSpec("v4", bf16_tflops=275.0, hbm_gbs=1228.0, hbm_gib=32.0,
                   device_kinds=("TPU v4",)),
    "v5e": ChipSpec("v5e", bf16_tflops=197.0, hbm_gbs=819.0, hbm_gib=16.0,
                    device_kinds=("TPU v5 lite", "TPU v5e")),
    "v5p": ChipSpec("v5p", bf16_tflops=459.0, hbm_gbs=2765.0, hbm_gib=95.0,
                    device_kinds=("TPU v5", "TPU v5p")),
    "v6e": ChipSpec("v6e", bf16_tflops=918.0, hbm_gbs=1640.0, hbm_gib=32.0,
                    device_kinds=("TPU v6 lite", "TPU v6e")),
}

#: the part an undeclared config is costed for when NO accelerator is
#: attached (CPU analysis runs); an attached TPU always answers for itself
DEFAULT_CHIP = "v5e"


def attached_chip() -> Optional[ChipSpec]:
    """The table row of the attached accelerator, or None on a non-TPU
    backend. A TPU whose ``device_kind`` the table does not hold is an error,
    never a default."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    for spec in CHIP_SPECS.values():
        if dev.device_kind in spec.device_kinds:
            return spec
    known = sorted(k for s in CHIP_SPECS.values() for k in s.device_kinds)
    raise ValueError(
        f"no ChipSpec for device kind {dev.device_kind!r}; known: {known} — "
        "add its peaks (with their source) to analysis/costs.py CHIP_SPECS"
    )


def declared_chip(spec, default_base: str = DEFAULT_CHIP) -> ChipSpec:
    """ChipSpec from a declaration: a table name, a dict of field overrides
    on top of ``base`` (default ``default_base``), or a ChipSpec. Pure — no
    device is asked."""
    if isinstance(spec, ChipSpec):
        return spec
    if isinstance(spec, str):
        if spec not in CHIP_SPECS:
            raise ValueError(
                f"unknown chip {spec!r}; known: {sorted(CHIP_SPECS)} "
                "(or pass a dict of ChipSpec fields)"
            )
        return CHIP_SPECS[spec]
    if isinstance(spec, dict):
        base_name = spec.get("base", default_base)
        if base_name not in CHIP_SPECS:
            raise ValueError(
                f"unknown chip base {base_name!r}; known: {sorted(CHIP_SPECS)}"
            )
        base = CHIP_SPECS[base_name]
        fields = base.to_dict()
        fields["name"] = "custom"
        fields.update({k: v for k, v in spec.items() if k != "base"})
        try:
            return ChipSpec(device_kinds=base.device_kinds, **fields)
        except TypeError as e:
            raise ValueError(f"bad chip spec fields {sorted(spec)}: {e}")
    raise TypeError(f"chip must be a name, dict, or ChipSpec; got {type(spec)}")


def resolve_chip(tpu_config=None, override=None) -> ChipSpec:
    """The ChipSpec rooflines divide through.

    On a TPU backend the attached device answers (its ``device_kind`` looked
    up in :data:`CHIP_SPECS`; an unknown kind raises), and a declaration —
    ``TpuConfig(chip=...)`` or ``override``, a name or a dict of overrides —
    that names a different part raises. With no accelerator attached the
    declaration alone decides (the analysis CLIs cost a program for a named
    part), defaulting to :data:`DEFAULT_CHIP`."""
    spec = override if override is not None else getattr(tpu_config, "chip", None)
    attached = attached_chip()
    if spec is None:
        return attached if attached is not None else CHIP_SPECS[DEFAULT_CHIP]
    if attached is None:
        return declared_chip(spec)
    declared = declared_chip(spec, default_base=attached.name)
    if declared.device_kinds != attached.device_kinds:
        raise ValueError(
            f"declared chip {declared.name!r} contradicts the attached "
            f"device ({attached.device_kinds[0]!r} = {attached.name!r})"
        )
    return declared


# ---------------------------------------------------------------------------
# pytree byte accounting (works on ShapeDtypeStructs and concrete arrays)
# ---------------------------------------------------------------------------

def tree_bytes(tree) -> int:
    """Total bytes of every leaf (shape x dtype — exact for quantized
    pytrees too, since int8 leaves carry their own dtype)."""
    import jax.tree_util as jtu

    total = 0
    for leaf in jtu.tree_leaves(tree):
        total += int(np.prod(leaf.shape)) * int(np.dtype(leaf.dtype).itemsize)
    return total


def tree_param_count(tree) -> int:
    import jax.tree_util as jtu

    return sum(int(np.prod(leaf.shape)) for leaf in jtu.tree_leaves(tree))


def _cache_itemsize(cache_struct) -> int:
    import jax.tree_util as jtu

    leaves = jtu.tree_leaves(cache_struct)
    if not leaves:
        return 2
    return int(np.dtype(leaves[0].dtype).itemsize)


# ---------------------------------------------------------------------------
# the analytic model (scan-aware: derived from arch/config, not HLO text)
# ---------------------------------------------------------------------------

def analytic_program_costs(
    wrapper, bucket: int, steps: int, param_count: int, param_bytes: int,
    kv_itemsize: int = 2,
) -> Dict[str, float]:
    """GLOBAL per-dispatch FLOPs and HBM bytes for one compiled program.

    The model mirrors what ``bench.py`` has always reported so the
    BENCH_*.json trajectory stays comparable:

    - matmul FLOPs: ``2 * param_count`` per token (the weight-streaming
      account; the embedding gather is counted like the reference did), with
      the lm_head paid once per *sampled* row in gather-last prefill;
    - attention FLOPs: ``QK^T + A.V`` over the attended window, halved for
      the causal prefill triangle;
    - HBM bytes: one full weight read per step plus the KV window
      read (decode) or KV write (prefill) at the cache store dtype.

    Multi-step programs (``steps`` > 1) pay everything per retired step —
    the lax.scan body re-streams weights and re-reads the window each
    iteration. Fused-speculation wrappers run a second (draft) stack; its
    weights already live in ``param_count``/``param_bytes`` (the app's
    struct covers both), so the weight-streaming terms are correct and only
    the attention/window terms are approximate for that program.
    """
    arch = wrapper.arch
    B = wrapper.batch_size
    decode_like = wrapper.attend_to_cache and not wrapper.prefill_to_cache
    L = arch.num_layers
    H = arch.num_attention_heads
    KV = arch.num_kv_heads
    D = arch.head_dim
    Dv = getattr(arch, "v_head_dim", None) or D
    lm_head = arch.vocab_size * arch.hidden_size

    if decode_like:
        active = max(1, wrapper.n_active_tokens)  # speculation windows: >1
        per_step_flops = (
            2.0 * param_count * B * active
            + 2.0 * L * H * (D + Dv) * bucket * B * active
        )
        per_step_kv_read = float(L * KV * (D + Dv) * bucket * B * kv_itemsize)
        flops = steps * per_step_flops
        hbm = steps * (float(param_bytes) + per_step_kv_read)
        kv_bytes = steps * per_step_kv_read
    else:
        tokens = B * bucket
        flops = (
            2.0 * (param_count - lm_head) * tokens
            + 2.0 * lm_head * B  # gather-last: lm_head on one row per batch
            + 1.0 * L * H * (D + Dv) * bucket * bucket * B  # causal triangle
        )
        kv_bytes = float(L * KV * (D + Dv) * bucket * B * kv_itemsize)
        hbm = float(param_bytes) + kv_bytes  # one weight read + the KV fill
    return {
        "flops": float(flops),
        "hbm_bytes": float(hbm),
        "weight_bytes": float(param_bytes),
        "kv_bytes": float(kv_bytes),
    }


# ---------------------------------------------------------------------------
# XLA's own counters (per-device module; None-tolerant on every backend)
# ---------------------------------------------------------------------------

def xla_cost_analysis(compiled) -> Optional[Dict[str, float]]:
    """``{"flops": ..., "bytes_accessed": ...}`` from
    ``compiled.cost_analysis()`` across its jax-version shapes (dict,
    list-of-dict, None), or None when unavailable/partial."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict) or "flops" not in ca:
        return None
    out = {"flops": float(ca["flops"])}
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out


def xla_memory_analysis(compiled) -> Optional[Dict[str, int]]:
    """argument/output/alias/temp byte sizes from
    ``compiled.memory_analysis()``, or None when the backend has none."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for key, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("alias_bytes", "alias_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("generated_code_bytes", "generated_code_size_in_bytes"),
    ):
        v = getattr(ma, attr, None)
        if v is None:
            return None
        out[key] = int(v)
    return out


# ---------------------------------------------------------------------------
# HBM-fit account (shared with the auditor's hbm_fit checker)
# ---------------------------------------------------------------------------

def hbm_residency(
    param_bytes: int, cache_bytes: int, world: int, chip: ChipSpec,
    memory: Optional[Dict[str, int]] = None,
) -> Dict[str, float]:
    """Per-chip HBM residency of one program while serving: sharded weights
    + the full allocated KV cache (= max-live KV across every bucket) +
    XLA's temp/scratch and non-aliased outputs when the backend reports
    them. Returns the breakdown plus ``fits``."""
    world = max(1, int(world))
    weights = param_bytes / world
    kv = cache_bytes / world
    temp = out_extra = 0.0
    if memory is not None:
        temp = float(memory.get("temp_bytes", 0))
        # donated caches alias outputs; only the non-aliased remainder is new
        out_extra = max(
            0.0, float(memory.get("output_bytes", 0)) - float(memory.get("alias_bytes", 0))
        )
    resident = weights + kv + temp + out_extra
    return {
        "weight_bytes_per_chip": weights,
        "kv_bytes_per_chip": kv,
        "temp_bytes": temp,
        "output_extra_bytes": out_extra,
        "resident_bytes": resident,
        "hbm_capacity_bytes": chip.hbm_bytes,
        "fits": resident <= chip.hbm_bytes,
    }


# ---------------------------------------------------------------------------
# CostSheet
# ---------------------------------------------------------------------------

#: XLA-vs-analytic FLOPs divergence beyond this ratio flags a mismatch
MISMATCH_RATIO = 2.0


@dataclass
class CostSheet:
    """The per-program cost account: canonical (analytic) FLOPs/bytes, the
    XLA cross-check, roofline classification, and the HBM-fit breakdown."""

    tag: str
    key: Any
    label: str
    bucket: int
    steps: int
    batch: int
    chip: ChipSpec
    world: int
    source: str  # "xla" (XLA analyses available) | "analytic" (fallback)
    flops: float  # canonical, PER CHIP per dispatch
    hbm_bytes: float  # canonical, PER CHIP per dispatch
    weight_bytes: float  # per chip
    kv_bytes: float  # per chip
    xla_flops: Optional[float] = None
    xla_bytes: Optional[float] = None
    memory: Optional[Dict[str, int]] = None
    fit: Dict[str, float] = field(default_factory=dict)
    mismatch: Optional[str] = None

    # -- roofline ----------------------------------------------------------
    @property
    def t_compute_s(self) -> float:
        return self.flops / self.chip.flops_per_s

    @property
    def t_hbm_s(self) -> float:
        return self.hbm_bytes / self.chip.bytes_per_s

    @property
    def floor_s(self) -> float:
        """Theoretical minimum dispatch latency on the declared chip."""
        return max(self.t_compute_s, self.t_hbm_s)

    @property
    def bound(self) -> str:
        return "compute" if self.t_compute_s >= self.t_hbm_s else "hbm"

    # -- the measured joins (bench.py AND the serving gauges use these, so
    # the BENCH trajectory and the Prometheus export share one formula) ----
    def mfu_pct(self, measured_s: float) -> float:
        if measured_s <= 0:
            return 0.0
        return 100.0 * self.flops / (measured_s * self.chip.flops_per_s)

    def hbm_bw_pct(self, measured_s: float) -> float:
        if measured_s <= 0:
            return 0.0
        return 100.0 * self.hbm_bytes / (measured_s * self.chip.bytes_per_s)

    def gap_ratio(self, measured_s: float) -> float:
        floor = self.floor_s
        return measured_s / floor if floor > 0 else 0.0

    def to_dict(self) -> dict:
        d = {
            "submodel": self.tag,
            "program": self.label,
            "bucket": self.bucket,
            "steps": self.steps,
            "batch": self.batch,
            "chip": self.chip.to_dict(),
            "world": self.world,
            "source": self.source,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "weight_bytes": self.weight_bytes,
            "kv_bytes": self.kv_bytes,
            "t_compute_s": self.t_compute_s,
            "t_hbm_s": self.t_hbm_s,
            "floor_s": self.floor_s,
            "bound": self.bound,
            "fit": self.fit,
        }
        if self.xla_flops is not None:
            d["xla_flops"] = self.xla_flops
        if self.xla_bytes is not None:
            d["xla_bytes"] = self.xla_bytes
        if self.memory is not None:
            d["memory"] = self.memory
        if self.mismatch:
            d["mismatch"] = self.mismatch
        return d


def program_cost_sheet(
    wrapper, key, prog=None, *, param_count: int, param_bytes: int,
    cache_bytes: int, kv_itemsize: int = 2, chip: Optional[ChipSpec] = None,
    compiled=None,
) -> CostSheet:
    """One CostSheet for one compiled-program slot. ``compiled`` (or
    ``prog._compiled``) supplies the XLA analyses when present; everything
    degrades to the analytic model — this function never raises on a
    backend that cannot answer."""
    from nxdi_tpu.runtime.model_wrapper import normalize_program_key

    tc = wrapper.config.tpu_config
    chip = chip or resolve_chip(tc)
    world = max(1, tc.tp_degree * getattr(tc, "pp_degree", 1))
    bucket, steps = normalize_program_key(key)
    label = getattr(prog, "label", f"{wrapper.tag}[{key}]") if prog is not None \
        else f"{wrapper.tag}[{key}]"

    ana = analytic_program_costs(
        wrapper, bucket, steps, param_count, param_bytes, kv_itemsize
    )
    if compiled is None and prog is not None:
        compiled = getattr(prog, "_compiled", None)
    xla = xla_cost_analysis(compiled) if compiled is not None else None
    memory = xla_memory_analysis(compiled) if compiled is not None else None

    sheet = CostSheet(
        tag=wrapper.tag,
        key=key,
        label=label,
        bucket=bucket,
        steps=steps,
        batch=wrapper.batch_size,
        chip=chip,
        world=world,
        source="xla" if xla is not None else "analytic",
        flops=ana["flops"] / world,
        hbm_bytes=ana["hbm_bytes"] / world,
        weight_bytes=ana["weight_bytes"] / world,
        kv_bytes=ana["kv_bytes"] / world,
        xla_flops=None if xla is None else xla["flops"],
        xla_bytes=None if xla is None else xla.get("bytes_accessed"),
        memory=memory,
    )
    sheet.fit = hbm_residency(param_bytes, cache_bytes, world, chip, memory)
    if sheet.xla_flops and sheet.flops > 0:
        # XLA's counter sees a lax.scan layer body ONCE (the stack is a
        # while loop in HLO), so on an L-layer scanned model its total is
        # legitimately up to ~L lower than the scan-aware analytic count —
        # widen the undercount bound by L before calling it a mismatch
        scan_layers = 1 if getattr(wrapper, "layers_unrolled", False) else max(
            1, getattr(wrapper.arch, "num_layers", 1)
        )
        # a stepped program (K-step scan window / device-loop cap rung)
        # repeats the WHOLE decode body in a while loop the counter also
        # sees once — the analytic side legitimately counts `steps` times
        # more, so the undercount bound widens by steps as well
        scan_layers *= max(1, steps or 1)
        ratio = sheet.xla_flops / sheet.flops
        if ratio > MISMATCH_RATIO or ratio < 1.0 / (MISMATCH_RATIO * scan_layers):
            sheet.mismatch = (
                f"XLA reports {sheet.xla_flops:.3g} FLOPs/chip vs analytic "
                f"{sheet.flops:.3g} ({ratio:.2f}x, scan-undercount allowance "
                f"{scan_layers}x) for {label} — one of the two models is not "
                "seeing this program's real work (pallas custom calls are "
                "invisible to XLA's counter; a changed lowering can also "
                "double-count masked attention)"
            )
            logger.warning("cost model mismatch: %s", sheet.mismatch)
    return sheet


# ---------------------------------------------------------------------------
# app-level sheets
# ---------------------------------------------------------------------------

def _app_struct_account(app) -> Tuple[int, int, int, int]:
    """(param_count, param_bytes, cache_bytes, kv_itemsize) from the app's
    abstract structs — no weights touched, identical for loaded apps."""
    params_struct = app.build_params_struct()
    cache_struct = app._cache_struct()
    return (
        tree_param_count(params_struct),
        tree_bytes(params_struct),
        tree_bytes(cache_struct),
        _cache_itemsize(cache_struct),
    )


def cost_sheets(
    app, *, chip=None, compile_missing: bool = False,
) -> List[CostSheet]:
    """A CostSheet for every (submodel, bucket[, steps]) program of an app.

    Programs already compiled (a loaded app's executables) are read in
    place — zero retracing, safe next to the hot path, like
    ``collective_summary``. With ``compile_missing`` (the CLI's mode on an
    unloaded app) uncompiled slots are lowered+compiled from abstract
    structs exactly like ``aot_compile``; a slot whose compile fails still
    gets its analytic sheet.
    """
    import jax
    import jax.tree_util as jtu

    app._build_wrappers()
    chip = resolve_chip(app.tpu_config, override=chip)
    params_struct = app.build_params_struct()
    cache_struct = app._cache_struct()
    param_count = tree_param_count(params_struct)
    param_bytes = tree_bytes(params_struct)
    cache_bytes = tree_bytes(cache_struct)
    kv_itemsize = _cache_itemsize(cache_struct)

    sheets: List[CostSheet] = []
    for tag, wrapper in app.models.items():
        ps = cs = None
        for bucket, steps, key, prog in wrapper.iter_programs():
            compiled = getattr(prog, "_compiled", None)
            if compiled is None and compile_missing:
                try:
                    if ps is None:
                        attach = lambda s, sh: jax.ShapeDtypeStruct(  # noqa: E731
                            s.shape, s.dtype, sharding=sh
                        )
                        ps = jtu.tree_map(attach, params_struct, wrapper._param_shardings)
                        cs = jtu.tree_map(attach, cache_struct, wrapper._cache_shardings)
                    with jax.set_mesh(wrapper._mesh):
                        compiled = prog.jitted.lower(
                            ps, cs, wrapper._example_for_key(key)
                        ).compile()
                except Exception as e:
                    logger.warning(
                        "cost sheet: could not compile %s (%s: %s); using the "
                        "analytic model", getattr(prog, "label", key),
                        type(e).__name__, e,
                    )
                    compiled = None
            sheets.append(program_cost_sheet(
                wrapper, key, prog,
                param_count=param_count, param_bytes=param_bytes,
                cache_bytes=cache_bytes, kv_itemsize=kv_itemsize,
                chip=chip, compiled=compiled,
            ))
    return sheets


def cost_summary(app) -> Dict[str, dict]:
    """Compact {program label: cost line} from a LOADED app's executables
    (no retracing) — what the bench probes print next to their latencies."""
    def sig(x: float) -> float:  # significant digits, not fixed decimals —
        return float(f"{x:.4g}")  # tiny test programs round to 0 otherwise

    out: Dict[str, dict] = {}
    for s in cost_sheets(app, compile_missing=False):
        out[s.label] = {
            "source": s.source,
            "gflops": sig(s.flops / 1e9),
            "hbm_mb": sig(s.hbm_bytes / 1e6),
            "bound": s.bound,
            "floor_ms": sig(s.floor_s * 1e3),
            "chip": s.chip.name,
        }
    return out


# ---------------------------------------------------------------------------
# the runtime join: registry attachment publishing the roofline gauges
# ---------------------------------------------------------------------------

def attach_cost_gauges(app) -> None:
    """Join the CostSheets to the live registry: on every telemetry export
    (snapshot / Prometheus scrape) the measured MEAN dispatch latency of
    each (submodel, bucket, steps) series — ``sum/count`` of the
    ``nxdi_dispatch_seconds`` histogram, which is exact, unlike a
    bucket-interpolated percentile — is divided through the program's
    CostSheet to set ``nxdi_program_mfu_pct`` / ``nxdi_program_hbm_bw_pct``
    / ``nxdi_roofline_gap_ratio``, and the sheet table rides the JSON
    snapshot as ``_cost_sheets``.

    The gauges measure *achieved vs declared-chip-peak*, so they are
    published only where the latency they divide by includes the device:
    ``sync_dispatch`` (``telemetry="full"``, or while a ``SubmodelProfiler``
    is attached). At ``"basic"`` the histogram times the enqueue, and a
    share of a peak computed from it is one no chip can give: the three
    series are then absent, not impossible. Attach errors never propagate
    into serving: the update recomputes lazily and any failure leaves the
    gauges unset.

    The hooks hold the app through a WEAK reference: ``app.telemetry`` owns
    the hook closures, so a strong capture would cycle app <-> telemetry
    and defeat the ``del app`` HBM-release idiom bench.py and the probes
    rely on between app builds — once the app is collected, the hooks
    quietly become no-ops.
    """
    import weakref

    tel = getattr(app, "telemetry", None)
    if tel is None or not tel.enabled:
        return
    if getattr(app, "_cost_gauges_attached", False):
        return
    app._cost_gauges_attached = True

    app_ref = weakref.ref(app)
    state: Dict[str, Any] = {"account": None, "memo": {}}

    def _sheets() -> List[CostSheet]:
        app = app_ref()
        if app is None:  # the app was freed; nothing to report
            return []
        if state["account"] is None:
            state["account"] = _app_struct_account(app)
        param_count, param_bytes, cache_bytes, kv_itemsize = state["account"]
        chip = resolve_chip(app.tpu_config)
        out = []
        for tag, wrapper in app.models.items():
            for bucket, steps, key, prog in wrapper.iter_programs():
                mk = (tag, str(key))
                cached = state["memo"].get(mk)
                compiled = getattr(prog, "_compiled", None)
                # refresh an analytic sheet once its program has compiled
                if cached is None or (
                    cached.source == "analytic" and compiled is not None
                ):
                    cached = program_cost_sheet(
                        wrapper, key, prog,
                        param_count=param_count, param_bytes=param_bytes,
                        cache_bytes=cache_bytes, kv_itemsize=kv_itemsize,
                        chip=chip, compiled=compiled,
                    )
                    state["memo"][mk] = cached
                out.append(cached)
        return out

    def _update() -> None:
        if not tel.sync_dispatch:
            return
        for sheet in _sheets():
            labels = dict(
                submodel=sheet.tag, bucket=str(sheet.bucket), steps=str(sheet.steps)
            )
            series = tel.dispatch_seconds.snapshot_series(**labels)
            if series is None or series.count == 0:
                continue
            mean_s = series.sum / series.count
            if mean_s <= 0:
                continue
            tel.program_mfu_pct.set(sheet.mfu_pct(mean_s), **labels)
            tel.program_hbm_bw_pct.set(sheet.hbm_bw_pct(mean_s), **labels)
            tel.roofline_gap_ratio.set(sheet.gap_ratio(mean_s), **labels)

    tel.attach(_update)
    tel.add_snapshot_extra(
        "_cost_sheets", lambda: [s.to_dict() for s in _sheets()]
    )
