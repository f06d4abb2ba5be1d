"""The checker suite the program auditor runs over every lowered submodel.

Each checker is a pure function ``(ProgramArtifacts) -> [Finding]`` over the
static views of one compiled program (jaxpr, StableHLO, optimized HLO, the
attention-strategy trace). Registered in :data:`CHECKERS`; the auditor runs
all of them unless told otherwise.

Checkers never raise on a violation — they return findings, so one bad
program cannot mask another's report. The CLI and the pytest wiring decide
what severity fails the run.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from nxdi_tpu.analysis import hlo as hlo_views
from nxdi_tpu.analysis.budget import expected_collective_budget, over_budget

#: captured constants larger than this are "a weight baked into the graph"
DEFAULT_CONST_THRESHOLD_BYTES = 512 * 1024

#: low-precision source dtypes whose upcast to fp32 counts as drift
_LOW_DTYPES = ("bfloat16", "float16", "float8_e4m3fn", "float8_e5m2")

#: function-name fragments (matched against the nxdi_tpu frames of an op's
#: traceback) where fp32 compute is intentional policy
DTYPE_DRIFT_ALLOWLIST = (
    "norm",        # rms_norm / layer_norm: fp32 variance per softmax_dtype
    "softmax",     # attention + sampling softmax
    "rotary",      # rope tables are fp32 by design
    "rope",
    "sample",      # sampling math on logits
    "topk",
    "top_k",
    "logit",       # logits processors / penalties
    "moe_router",  # router softmax precision
    "quantized_linear",  # activation-quantize scale math is fp32 by design;
                         # the actual contraction dtype is policed by the
                         # quantized_dtype checker instead
)


@dataclass
class Finding:
    """One violation (or notable observation) for one compiled program."""

    checker: str
    severity: str  # "error" | "warning"
    submodel: str
    program: str  # e.g. "token_generation_model[64]" / "tkg_multistep[k4,128]"
    message: str

    def to_dict(self) -> Dict[str, str]:
        return {
            "checker": self.checker,
            "severity": self.severity,
            "submodel": self.submodel,
            "program": self.program,
            "message": self.message,
        }

    def __str__(self) -> str:
        return f"[{self.severity}] {self.program} {self.checker}: {self.message}"


@dataclass
class ProgramArtifacts:
    """Everything a checker may look at for one (submodel, bucket) program."""

    wrapper: Any  # ModelWrapper
    tag: str
    key: Any  # bucket int, or (steps, bucket) for multi-step programs
    label: str
    config: Any  # InferenceConfig
    arch: Any  # DecoderArch
    jaxpr: Any = None  # ClosedJaxpr (None if tracing unavailable)
    stablehlo: Optional[str] = None
    hlo: Optional[str] = None
    strategies: Tuple[str, ...] = ()
    n_param_leaves: int = 0
    cache_paths: Tuple[str, ...] = ()
    kept_args: Optional[Tuple[int, ...]] = None  # flat indices kept by lowering
    donated_flags: Optional[Tuple[bool, ...]] = None  # per flat arg
    const_threshold: int = DEFAULT_CONST_THRESHOLD_BYTES
    collectives: Dict[str, int] = field(default_factory=dict)
    compiled: Any = None  # the compiled executable (memory/cost analyses)
    param_bytes: int = 0  # GLOBAL weight bytes (abstract params struct)
    cache_bytes: int = 0  # GLOBAL allocated KV bytes (= max-live KV)
    #: abstract params pytree WITH shardings attached (what aot_compile
    #: lowers against) — lets checkers reason about per-leaf PartitionSpecs
    params_struct: Any = None
    #: one dict per audit run, shared by every program's artifacts — lets a
    #: checker run program-independent passes once instead of re-emitting
    #: identical findings per (submodel, bucket)
    shared: Any = None

    @property
    def tc(self):
        return self.config.tpu_config

    def finding(self, checker: str, message: str, severity: str = "error") -> Finding:
        return Finding(checker, severity, self.tag, self.label, message)


# ---------------------------------------------------------------------------
# 1. donation audit
# ---------------------------------------------------------------------------

def check_donation(art: ProgramArtifacts) -> List[Finding]:
    """Every KV-cache input must alias an output buffer, or decode holds two
    copies of the cache in HBM for the life of the program."""
    if art.stablehlo is None:
        return [art.finding("donation", "no StableHLO available to audit",
                            severity="warning")]
    findings: List[Finding] = []
    aliased = hlo_views.aliased_arg_positions(art.stablehlo)
    n_cache = len(art.cache_paths)

    if art.kept_args is not None:
        kept = sorted(art.kept_args)
        pos_of_flat = {flat: pos for pos, flat in enumerate(kept)}
        for ci, path in enumerate(art.cache_paths):
            flat = art.n_param_leaves + ci
            if art.donated_flags is not None and not art.donated_flags[flat]:
                findings.append(art.finding(
                    "donation",
                    f"cache input '{path}' was compiled WITHOUT donation "
                    "(donate_argnums missing) — the program keeps a second "
                    "copy of this cache buffer",
                ))
                continue
            if flat not in pos_of_flat:
                findings.append(art.finding(
                    "donation",
                    f"cache input '{path}' is unused by the compiled program "
                    "(pruned from the signature) — a decode program that "
                    "never reads its cache is miswired",
                    severity="warning",
                ))
                continue
            if pos_of_flat[flat] not in aliased:
                findings.append(art.finding(
                    "donation",
                    f"cache input '{path}' is donated but did NOT resolve to "
                    "an input/output alias — XLA will materialize a second "
                    f"{path} buffer (check output sharding/layout drift on "
                    "the donated round trip)",
                ))
        return findings

    # fallback when kept_var_idx is unavailable: count aliases vs cache leaves
    if len(aliased) < n_cache:
        findings.append(art.finding(
            "donation",
            f"only {len(aliased)} of {n_cache} cache inputs resolved to an "
            "input/output alias — at least one cache buffer is doubled "
            f"(cache leaves: {', '.join(art.cache_paths)})",
        ))
    return findings


# ---------------------------------------------------------------------------
# 2. collective budget
# ---------------------------------------------------------------------------

def check_collectives(art: ProgramArtifacts) -> List[Finding]:
    """Observed collective counts must stay within the budget derived from
    the config's expected ShardingPolicy (a typo'd policy inserts extras)."""
    if art.hlo is None:
        return [art.finding("collectives", "no optimized HLO available to audit",
                            severity="warning")]
    observed = art.collectives or hlo_views.collective_counts(art.hlo)
    art.collectives = observed
    budget, explain = expected_collective_budget(art.tc, art.arch, art.wrapper)
    findings = []
    for op, (got, allowed) in over_budget(observed, budget).items():
        why = "; ".join(explain) if explain else "no collectives budgeted"
        findings.append(art.finding(
            "collectives",
            f"{got} {op} ops in the compiled program exceed the policy "
            f"budget of {allowed} — an unexplained collective usually means "
            "a sharding-policy regression (budget: " + why + ")",
        ))
    return findings


# ---------------------------------------------------------------------------
# 3. dtype-drift lint
# ---------------------------------------------------------------------------

def _nxdi_frames(eqn) -> List[Tuple[str, str]]:
    """(file, function) pairs of the eqn's traceback inside this package."""
    tb = getattr(eqn.source_info, "traceback", None)
    out = []
    if tb is None:
        return out
    for f in tb.frames:
        if "nxdi_tpu" in f.file_name:
            import os

            out.append((os.path.basename(f.file_name), f.function_name))
    return out


def _walk_jaxprs(jaxpr, visit: Callable[[Any], None]) -> None:
    """Depth-first over a Jaxpr and every nested (closed) jaxpr in eqn params."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        stack = list(eqn.params.values())
        while stack:
            v = stack.pop()
            if hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
                _walk_jaxprs(v.jaxpr, visit)  # ClosedJaxpr
            elif hasattr(v, "eqns"):
                _walk_jaxprs(v, visit)  # raw Jaxpr
            elif isinstance(v, (list, tuple)):
                stack.extend(v)


def check_dtype_drift(art: ProgramArtifacts) -> List[Finding]:
    """Flag fp32 intermediates materialized from low-precision values outside
    the allowlisted islands (norms, softmax, rope, sampling logits)."""
    if art.jaxpr is None:
        return [art.finding("dtype_drift", "no jaxpr available to audit",
                            severity="warning")]
    vocab = getattr(art.arch, "vocab_size", -1)
    hits: List[Tuple[Tuple[int, ...], List[Tuple[str, str]]]] = []

    def visit(eqn):
        if eqn.primitive.name != "convert_element_type":
            return
        src = str(eqn.invars[0].aval.dtype)
        dst = str(eqn.outvars[0].aval.dtype)
        if src not in _LOW_DTYPES or dst not in ("float32", "float64"):
            return
        shape = tuple(eqn.outvars[0].aval.shape)
        if shape and shape[-1] == vocab:
            return  # sampling logits: fp32 on purpose
        frames = _nxdi_frames(eqn)
        names = " ".join(fn for _, fn in frames).lower()
        if any(allowed in names for allowed in DTYPE_DRIFT_ALLOWLIST):
            return
        hits.append((shape, frames[:3]))

    _walk_jaxprs(art.jaxpr.jaxpr, visit)
    findings, seen = [], set()
    for shape, frames in hits:
        where = " <- ".join(f"{fn} ({f})" for f, fn in frames) or "<no traceback>"
        msg = (
            f"low-precision value upcast to fp32 at {where} (result shape "
            f"{shape}) outside the allowlisted fp32 islands "
            f"({', '.join(DTYPE_DRIFT_ALLOWLIST[:4])}, ...) — a silent fp32 "
            "path doubles the bytes this intermediate streams"
        )
        if msg not in seen:
            seen.add(msg)
            findings.append(art.finding("dtype_drift", msg))
    return findings


# ---------------------------------------------------------------------------
# 4. baked-constant lint
# ---------------------------------------------------------------------------

def check_baked_constants(art: ProgramArtifacts) -> List[Finding]:
    """Any captured constant above the size threshold is almost certainly a
    weight closed over instead of passed as an argument — it is duplicated
    into every program that closes over it and re-uploaded per executable."""
    if art.jaxpr is None:
        return [art.finding("baked_constants", "no jaxpr available to audit",
                            severity="warning")]
    findings = []

    def scan_consts(consts):
        for c in consts:
            try:
                nbytes = int(np.asarray(c).nbytes)
                shape = tuple(np.asarray(c).shape)
                dtype = str(np.asarray(c).dtype)
            except Exception:
                continue
            if nbytes > art.const_threshold:
                findings.append(art.finding(
                    "baked_constants",
                    f"captured constant {dtype}{list(shape)} ({nbytes} bytes "
                    f"> threshold {art.const_threshold}) is baked into the "
                    "graph — pass it as a program argument so it is stored "
                    "once and shared across programs",
                ))

    scan_consts(art.jaxpr.consts)

    def visit(eqn):
        for v in eqn.params.values():
            if hasattr(v, "consts"):
                scan_consts(v.consts)
            elif isinstance(v, (list, tuple)):
                for x in v:
                    if hasattr(x, "consts"):
                        scan_consts(x.consts)

    _walk_jaxprs(art.jaxpr.jaxpr, visit)
    return findings


# ---------------------------------------------------------------------------
# 5. required kernel strategies (absorbed from _AutoLayoutProgram)
# ---------------------------------------------------------------------------

def missing_required_strategies(
    strategies: Tuple[str, ...], required
) -> List[Tuple[str, Tuple[str, ...]]]:
    """``[(flag, acceptable_names), ...]`` for every enabled kernel flag none
    of whose strategies engaged in the traced program. Shared by the runtime
    lowering check (runtime/model_wrapper.py) and the audit-time checker."""
    missing = []
    for flag, names in required:
        if not any(n in strategies for n in names):
            missing.append((flag, tuple(names)))
    return missing


def required_strategy_error(label: str, flag: str, names) -> str:
    return (
        f"{label}: {flag} is enabled but none of its kernel "
        f"strategies {tuple(names)} engaged in the compiled program — "
        "the flag would be a silent no-op for this model/config; "
        "disable it or use a supported configuration"
    )


def check_required_strategies(art: ProgramArtifacts) -> List[Finding]:
    required = art.wrapper._required_strategies()
    findings = []
    for flag, names in missing_required_strategies(art.strategies, required):
        findings.append(art.finding(
            "required_strategies", required_strategy_error(art.label, flag, names)
        ))
    return findings


# ---------------------------------------------------------------------------
# 6. KV-layout addressing
# ---------------------------------------------------------------------------

def check_kv_layout(art: ProgramArtifacts) -> List[Finding]:
    """Block-KV addressing inputs must be provably LIVE where the layout
    needs them and provably DEAD everywhere else (via ``kept_var_idx``):

    - paged programs: ``slot_mapping`` (the write path) must be live in every
      program, and ``block_table`` (the pool read path) in cache-attending
      programs — a dead one compiles fine today but routes KV writes/reads
      nowhere;
    - non-paged programs: a live ``slot_mapping``/``block_table`` input means
      the program consumes paged addressing no host code maintains — a
      layout-input mixup.
    """
    from nxdi_tpu.kvcache.kv_cache import BlockKVLayout

    paged = isinstance(getattr(art.wrapper, "layout", None), BlockKVLayout)
    try:
        example = art.wrapper._example_for_key(art.key)
    except Exception as e:
        return [art.finding(
            "kv_layout", f"example batch unavailable: {type(e).__name__}: {e}",
            severity="warning",
        )]
    keys = sorted(example)  # jax flattens dicts in sorted-key order
    present = [k for k in ("block_table", "slot_mapping") if k in keys]
    findings: List[Finding] = []
    if paged and "slot_mapping" not in present:
        findings.append(art.finding(
            "kv_layout",
            "paged program has no 'slot_mapping' batch input — the compiled "
            "program cannot address the block pool",
        ))
    if not present:
        return findings
    if art.kept_args is None:
        return findings + [art.finding(
            "kv_layout",
            "kept_var_idx unavailable; cannot prove layout-input liveness",
            severity="warning",
        )]
    kept = set(art.kept_args)
    n_fixed = art.n_param_leaves + len(art.cache_paths)
    # liveness required per input: the write path always, the read path only
    # in programs that attend the cache through the block table
    required_live = {"slot_mapping": True,
                     "block_table": bool(getattr(art.wrapper, "attend_to_cache", False))}
    for k in present:
        live = (n_fixed + keys.index(k)) in kept
        if paged and required_live[k] and not live:
            findings.append(art.finding(
                "kv_layout",
                f"paged program DROPPED its '{k}' input (pruned by "
                "kept_var_idx) — block-KV addressing is provably unused, so "
                "cache writes/reads route nowhere; the forward is not "
                "consuming the paged layout's inputs",
            ))
        elif not paged and live:
            findings.append(art.finding(
                "kv_layout",
                f"non-paged program KEEPS a live '{k}' input — it consumes "
                "paged addressing that no host code maintains for this "
                "layout (layout-input mixup)",
            ))
    return findings


# ---------------------------------------------------------------------------
# 6b. mixed prefill+decode dispatch
# ---------------------------------------------------------------------------

def check_mixed_program(art: ProgramArtifacts) -> List[Finding]:
    """The mixed-dispatch program packs prefill chunks and decode singles of
    R slots into one token stream, so its correctness hangs on three ragged
    row-descriptor inputs reaching the compiled program ALIVE (the kv_layout
    recipe, via ``kept_var_idx``):

    - ``mixed_row_ids``: per-token slot ownership — a pruned one means the
      kernel attends every token to every row's KV (cross-request leakage);
    - ``block_table`` / ``slot_mapping``: the combined R-row pool read and
      per-token write paths;

    and on the KV cache being donated: the packed program both reads and
    commits KV in one launch, so a non-donated cache doubles HBM for the
    largest program in the ladder.
    """
    from nxdi_tpu.runtime.model_wrapper import TAG_MIXED

    if art.tag != TAG_MIXED:
        return []
    try:
        example = art.wrapper._example_for_key(art.key)
    except Exception as e:
        return [art.finding(
            "mixed_program",
            f"example batch unavailable: {type(e).__name__}: {e}",
            severity="warning",
        )]
    keys = sorted(example)  # jax flattens dicts in sorted-key order
    findings: List[Finding] = []
    required = ("mixed_row_ids", "block_table", "slot_mapping")
    missing = [k for k in required if k not in keys]
    if missing:
        return [art.finding(
            "mixed_program",
            f"mixed program is missing batch input(s) {missing} — the packed "
            "token stream cannot be attributed to slots or addressed into "
            "the block pool",
        )]
    n_fixed = art.n_param_leaves + len(art.cache_paths)
    if art.kept_args is None:
        findings.append(art.finding(
            "mixed_program",
            "kept_var_idx unavailable; cannot prove ragged row-descriptor "
            "liveness", severity="warning",
        ))
    else:
        kept = set(art.kept_args)
        for k in required:
            if (n_fixed + keys.index(k)) not in kept:
                findings.append(art.finding(
                    "mixed_program",
                    f"mixed program DROPPED its '{k}' input (pruned by "
                    "kept_var_idx) — the ragged row descriptors are provably "
                    "unused, so packed tokens either attend across requests "
                    "or route KV nowhere",
                ))
    if art.donated_flags is not None:
        for ci, path in enumerate(art.cache_paths):
            if not art.donated_flags[art.n_param_leaves + ci]:
                findings.append(art.finding(
                    "mixed_program",
                    f"mixed program cache input '{path}' compiled WITHOUT "
                    "donation — the single-launch read+commit program would "
                    "hold two cache copies at its largest token bucket",
                ))
    return findings


# ---------------------------------------------------------------------------
# 6c. device-resident decode loop
# ---------------------------------------------------------------------------

def _jaxpr_has_while(jaxpr) -> bool:
    """True iff a ``while`` primitive appears anywhere in ``jaxpr`` —
    including inside nested call/scan/cond sub-jaxprs. The check must run
    on the JAXPR, not the StableHLO: ``lax.scan`` (the layer stack) also
    lowers to ``stablehlo.while``, so the text alone cannot distinguish a
    data-dependent decode loop from a fixed-trip layer scan."""
    seen: list = [jaxpr]
    while seen:
        j = seen.pop()
        for eqn in j.eqns:
            if eqn.primitive.name == "while":
                return True
            for v in eqn.params.values():
                for x in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(x, "jaxpr", x)
                    if hasattr(inner, "eqns"):
                        seen.append(inner)
    return False


def check_device_loop(art: ProgramArtifacts) -> List[Finding]:
    """The ``tkg_device_loop`` program amortizes host dispatch over a
    data-dependent number of decode steps, so its correctness hangs on
    three static properties of the lowered program:

    - an actual ``while`` loop in the traced program: a loop that traced
      away (folded/unrolled to a fixed chain) silently reverts to
      fixed-rung semantics and the per-row exit is gone;
    - the per-row halt vectors ``budget_steps`` and ``eos_token_ids``
      surviving lowering ALIVE (the kv_layout recipe, via
      ``kept_var_idx``): a pruned one means rows cannot exit early — every
      lane runs to the cap and the host receives tokens past EOS/budget;
    - the KV-cache carry donated through the loop body: the body reads and
      commits KV every iteration, so a non-donated cache doubles HBM for
      the whole launch.
    """
    from nxdi_tpu.runtime.model_wrapper import TAG_DEVICE_LOOP

    if art.tag != TAG_DEVICE_LOOP:
        return []
    findings: List[Finding] = []
    if art.jaxpr is None:
        findings.append(art.finding(
            "device_loop",
            "traced jaxpr unavailable; cannot prove the decode while-loop "
            "survived tracing", severity="warning",
        ))
    elif not _jaxpr_has_while(art.jaxpr.jaxpr):
        findings.append(art.finding(
            "device_loop",
            "no while primitive in the traced program (stablehlo.while "
            "alone cannot prove it: the layer scan lowers to one too) — "
            "the decode loop traced away, so the launch cannot run a "
            "data-dependent number of steps",
        ))
    try:
        example = art.wrapper._example_for_key(art.key)
    except Exception as e:
        return findings + [art.finding(
            "device_loop",
            f"example batch unavailable: {type(e).__name__}: {e}",
            severity="warning",
        )]
    keys = sorted(example)  # jax flattens dicts in sorted-key order
    required = ("budget_steps", "eos_token_ids")
    missing = [k for k in required if k not in keys]
    if missing:
        return findings + [art.finding(
            "device_loop",
            f"device-loop program is missing batch input(s) {missing} — the "
            "in-graph per-row halt has nothing to compare against",
        )]
    n_fixed = art.n_param_leaves + len(art.cache_paths)
    if art.kept_args is None:
        findings.append(art.finding(
            "device_loop",
            "kept_var_idx unavailable; cannot prove halt-vector liveness",
            severity="warning",
        ))
    else:
        kept = set(art.kept_args)
        for k in required:
            if (n_fixed + keys.index(k)) not in kept:
                findings.append(art.finding(
                    "device_loop",
                    f"device-loop program DROPPED its '{k}' input (pruned "
                    "by kept_var_idx) — the per-row halt is provably "
                    "unused, so every lane runs to the cap and emits past "
                    "its EOS/budget exit",
                ))
    if art.donated_flags is not None:
        for ci, path in enumerate(art.cache_paths):
            if not art.donated_flags[art.n_param_leaves + ci]:
                findings.append(art.finding(
                    "device_loop",
                    f"device-loop cache input '{path}' compiled WITHOUT "
                    "donation — the while-loop body reads and commits KV "
                    "every iteration, so the launch holds two cache copies",
                ))
    return findings


# ---------------------------------------------------------------------------
# 7. LoRA adapter sharding
# ---------------------------------------------------------------------------

def _spec_axes(leaf, dim: int, mesh=None):
    """EFFECTIVE mesh axes a leaf's PartitionSpec assigns to array dim
    ``dim`` (as a tuple; () = unsharded). Specs shorter than the array rank
    leave the trailing dims unsharded (GSPMD trailing rule); size-1 mesh
    axes shard nothing, so they are dropped — ``("ep", "epx", "tp")`` and
    ``("tp",)`` agree on a non-MoE mesh and genuinely differ once ep > 1."""
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    entries = tuple(spec) if spec is not None else ()
    rank = len(getattr(leaf, "shape", ()))
    entries = entries + (None,) * max(0, rank - len(entries))
    e = entries[dim] if dim < len(entries) else None
    if e is None:
        return ()
    axes = tuple(e) if isinstance(e, (tuple, list)) else (e,)
    if mesh is not None:
        sizes = dict(mesh.shape)
        axes = tuple(a for a in axes if sizes.get(a, 1) > 1)
    return axes


def _lora_spec_findings(art: ProgramArtifacts, lc) -> List[Finding]:
    """The program-independent half of the LoRA audit: adapter A/B buffer
    PartitionSpecs vs their base projections (see check_lora_sharding)."""
    ps = art.params_struct
    layers = ps.get("layers") if isinstance(ps, dict) else None
    if not isinstance(layers, dict):
        return [art.finding(
            "lora_sharding", "params struct unavailable; cannot audit LoRA "
            "buffer shardings", severity="warning",
        )]
    from nxdi_tpu.lora.serving import LORA_TARGETABLE_MODULES

    findings: List[Finding] = []
    for name in lc.target_modules:
        group, proj = LORA_TARGETABLE_MODULES[name][0]
        p = layers.get(group, {}).get(proj)
        if not isinstance(p, dict) or "lora_A" not in p:
            continue
        base = p.get("w", p.get("qw"))
        if base is None:
            continue
        mesh = getattr(art.wrapper, "_mesh", None)
        rank_w = len(base.shape)
        in_w = _spec_axes(base, rank_w - 2, mesh)
        out_w = _spec_axes(base, rank_w - 1, mesh)
        in_a = _spec_axes(p["lora_A"], 2, mesh)
        out_b = _spec_axes(p["lora_B"], 3, mesh)
        rank_axes = _spec_axes(p["lora_A"], 3, mesh) + _spec_axes(
            p["lora_B"], 2, mesh
        )
        if in_a != in_w:
            findings.append(art.finding(
                "lora_sharding",
                f"{group}.{proj}: lora_A shards its in-features dim on axes "
                f"{in_a or '()'} but the base weight shards on "
                f"{in_w or '()'} — the adapter delta no longer decomposes "
                "the sharded projection in place, so GSPMD inserts a "
                "per-layer gather/reshard",
            ))
        if out_b != out_w:
            findings.append(art.finding(
                "lora_sharding",
                f"{group}.{proj}: lora_B shards its out-features dim on axes "
                f"{out_b or '()'} but the base weight shards on "
                f"{out_w or '()'} — a replicated adapter next to an "
                "mp-sharded weight silently all-gathers per layer",
            ))
        if rank_axes:
            findings.append(art.finding(
                "lora_sharding",
                f"{group}.{proj}: the LoRA rank dim is sharded on "
                f"{rank_axes} — the low-rank contraction becomes a per-layer "
                "cross-shard reduce; keep the rank dim replicated",
            ))
    return findings


def check_lora_sharding(art: ProgramArtifacts) -> List[Finding]:
    """LoRA adapter buffers must shard on the SAME mesh axes as the base
    projections they rank-decompose (lora/serving.py layout: ``lora_A``
    (L, S, in, r), ``lora_B`` (L, S, r, out) next to a base ``w``/``qw``
    (L, in, out)):

    - column-parallel base (out dim sharded): ``lora_B``'s out dim must
      carry the same axes — a replicated ``lora_B`` next to an mp-sharded
      weight makes GSPMD all-gather the delta (or reshard the activations)
      EVERY layer;
    - row-parallel base (in dim sharded): same for ``lora_A``'s in dim;
    - the rank dim must stay unsharded on both (a sharded contraction dim
      inserts a per-layer reduce);
    - ``adapter_ids`` routing must stay batch-replicated: every row's
      adapter gather happens on every shard, so a sharded id vector would
      route different adapters on different shards.
    """
    lc = getattr(art.tc, "lora_config", None)
    if lc is None:
        return []
    findings: List[Finding] = []
    # the buffer-spec comparison reads only the audit-wide params struct +
    # adapter spec layout — program-independent, so run it ONCE per audit
    # rather than re-emitting identical findings per (submodel, bucket)
    shared = art.shared
    run_specs = shared is None or not shared.get("lora_spec_checked")
    if shared is not None:
        shared["lora_spec_checked"] = True
    if run_specs:
        findings.extend(_lora_spec_findings(art, lc))
    # adapter_ids routing: the batch input must be fully replicated. Scan
    # every positional arg for the entry rather than assuming its position —
    # a reordered aot_compile signature must degrade to "not found", never
    # to auditing the wrong input.
    for arg in art.compiled.input_shardings[0]:
        sh = arg.get("adapter_ids") if isinstance(arg, dict) else None
        if sh is not None and not getattr(sh, "is_fully_replicated", True):
            findings.append(art.finding(
                "lora_sharding",
                "the 'adapter_ids' batch input is not batch-replicated "
                f"(compiled sharding {sh}) — shards would gather DIFFERENT "
                "adapters for the same row",
            ))
    return findings


# ---------------------------------------------------------------------------
# 8. quantized-path dtype rules
# ---------------------------------------------------------------------------

#: elementwise-ish primitives a dequant/quantize chain may pass through
#: between a convert and the dot it feeds
_QDQ_CHAIN_PRIMS = (
    "convert_element_type", "mul", "div", "add", "sub", "max", "min",
    "round", "nearbyint", "clamp", "broadcast_in_dim", "reshape",
    "transpose", "squeeze", "expand_dims", "select_n", "abs", "neg",
    "stop_gradient",
    # jnp.round / jnp.clip lower as small pjit/custom_jvp wrapper eqns —
    # flow through them (their invars) or every quantize chain dead-ends
    # one hop from the dot
    "pjit", "custom_jvp_call", "custom_vjp_call", "closed_call",
)

_INT8_DTYPES = ("int8", "uint8", "float8_e4m3fn", "float8_e5m2")


def _scan_quantized_dots(jaxpr, on_dot) -> None:
    """Depth-first over every (sub)jaxpr; calls ``on_dot(eqn, defs)`` for
    each ``dot_general`` with that jaxpr level's ``{var: producing eqn}``
    map — quantize/dequant chains never cross a scan boundary, so per-level
    dataflow is exact for this audit."""
    defs = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            defs[ov] = eqn
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            on_dot(eqn, defs)
        stack = list(eqn.params.values())
        while stack:
            v = stack.pop()
            if hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
                _scan_quantized_dots(v.jaxpr, on_dot)
            elif hasattr(v, "eqns"):
                _scan_quantized_dots(v, on_dot)
            elif isinstance(v, (list, tuple)):
                stack.extend(v)


def _chain_reaches(var, defs, match, max_depth: int = 16):
    """The first eqn satisfying ``match(eqn)`` reachable BACKWARD from
    ``var`` through elementwise/layout ops (None if the chain dead-ends
    into a real compute op, an argument, or the depth bound). Non-matching
    chain ops — including intermediate converts — are walked THROUGH, so a
    layered ``int8 -> f32 -> bf16`` dequant still attributes to its int8
    origin."""
    seen = set()
    frontier = [(var, 0)]
    while frontier:
        v, depth = frontier.pop()
        if depth > max_depth or id(v) in seen:
            continue
        seen.add(id(v))
        eqn = defs.get(v)
        if eqn is None:
            continue
        name = eqn.primitive.name
        if match(eqn):
            return eqn
        if name in _QDQ_CHAIN_PRIMS or name.startswith("reduce_"):
            for iv in eqn.invars:
                if hasattr(iv, "aval"):
                    frontier.append((iv, depth + 1))
    return None


def check_quantized_dtype(art: ProgramArtifacts) -> List[Finding]:
    """Quantized-path dtype rules for the w8a8 MXU path
    (``quantized=True`` + ``activation_quantization_type``):

    - **un-upcast reach**: at least one ``dot_general`` must contract
      int8 x int8 operands — a program that declared the int8 MXU path but
      upcasts/dequantizes before every dot (an fp32 detour between the
      dequant scale and the dot) silently pays full-precision matmul
      bandwidth while reporting int8 throughput;
    - **static scales are constants**: under
      ``activation_quantization_type="static"`` the calibrated
      ``input_scale`` is a checkpoint constant — an int8 dot whose quantize
      chain contains a per-token ``reduce_max`` means the hot path is
      recomputing the scale the calibration was supposed to eliminate.

    Weight-only quantization (no activation quant) upcasts INTO the matmul
    by design (dequantize-on-read) and is out of scope here.
    """
    tc = art.tc
    aq = getattr(tc, "activation_quantization_type", None)
    if not getattr(tc, "quantized", False) or aq not in ("dynamic", "static"):
        return []
    if art.jaxpr is None:
        return [art.finding("quantized_dtype", "no jaxpr available to audit",
                            severity="warning")]

    int8_dots: List[Tuple[Any, dict]] = []
    detours: List[str] = []

    def on_dot(eqn, defs):
        dts = [str(iv.aval.dtype) for iv in eqn.invars[:2]]
        if all(d in _INT8_DTYPES for d in dts):
            int8_dots.append((eqn, defs))
            return
        # a float dot whose operand chain passes through an int8 upcast is
        # the dequant-before-dot detour (record one attribution per shape)
        def from_int8(e):
            return (
                e.primitive.name == "convert_element_type"
                and str(e.invars[0].aval.dtype) in _INT8_DTYPES
            )

        for iv in eqn.invars[:2]:
            if not str(iv.aval.dtype).startswith("float"):
                continue
            cvt = _chain_reaches(iv, defs, from_int8)
            if cvt is not None:
                frames = _nxdi_frames(cvt)
                where = " <- ".join(f"{fn} ({f})" for f, fn in frames[:3])
                detours.append(
                    f"dot of shape {tuple(eqn.outvars[0].aval.shape)} consumes "
                    f"an int8 weight upcast to {iv.aval.dtype} before the "
                    f"contraction ({where or 'no traceback'})"
                )

    _scan_quantized_dots(art.jaxpr.jaxpr, on_dot)

    findings: List[Finding] = []
    if not int8_dots:
        hint = ("; ".join(detours[:2])) or "no int8 contraction found at all"
        findings.append(art.finding(
            "quantized_dtype",
            f"activation_quantization_type={aq!r} declares the int8 MXU "
            "path, but NO dot_general contracts int8 x int8 operands — the "
            f"dequant happens before the dot (fp32 detour: {hint}); the "
            "program pays full-precision matmul bandwidth while the config "
            "promises w8a8",
        ))
    if aq == "static":
        # the per-token amax reduction lives inside quantized_linear
        # (ops/quantization.py) — attribute by traceback like dtype_drift,
        # which survives the pjit/scan jaxpr nesting the dataflow walk
        # cannot cross. The KV-quant amax (kvcache/) never matches.
        recomputes = []

        def visit(eqn):
            if not eqn.primitive.name.startswith("reduce_max"):
                return
            for fname, fn in _nxdi_frames(eqn):
                if fname == "quantization.py" and "quantized_linear" in fn:
                    recomputes.append(eqn)
                    return

        _walk_jaxprs(art.jaxpr.jaxpr, visit)
        if recomputes:
            findings.append(art.finding(
                "quantized_dtype",
                "static activation quantization declared, but the program "
                f"contains {len(recomputes)} per-token reduce_max amax "
                "reduction(s) inside quantized_linear — the input scale is "
                "being RECOMPUTED on the hot path instead of consumed as "
                "the calibrated input_scale constant",
            ))
    return findings


# ---------------------------------------------------------------------------
# 9. HBM fit
# ---------------------------------------------------------------------------

def check_hbm_fit(art: ProgramArtifacts) -> List[Finding]:
    """Weights + the full allocated KV cache (max-live across every bucket)
    + XLA's temp/scratch must fit the declared chip's per-chip HBM. The
    budget derives from the sharding world like analysis/budget.py derives
    collective budgets — an over-provisioned ``seq_len * kv_cache_batch``
    product fails here at audit time instead of OOMing at load."""
    from nxdi_tpu.analysis.costs import (
        hbm_residency,
        resolve_chip,
        xla_memory_analysis,
    )

    tc = art.tc
    chip = resolve_chip(tc)
    world = max(1, tc.tp_degree * getattr(tc, "pp_degree", 1))
    memory = xla_memory_analysis(art.compiled) if art.compiled is not None else None
    fit = hbm_residency(art.param_bytes, art.cache_bytes, world, chip, memory)
    if fit["fits"]:
        return []

    def gib(x: float) -> str:
        return f"{x / 2.0 ** 30:.3f} GiB"

    return [art.finding(
        "hbm_fit",
        f"per-chip HBM residency {gib(fit['resident_bytes'])} exceeds the "
        f"{chip.name} capacity {gib(fit['hbm_capacity_bytes'])}: weights "
        f"{gib(fit['weight_bytes_per_chip'])} + max-live KV "
        f"{gib(fit['kv_bytes_per_chip'])} + temp {gib(fit['temp_bytes'])} "
        f"+ non-aliased outputs {gib(fit['output_extra_bytes'])} over a "
        f"{world}-chip world — shrink seq_len/kv_cache_batch_size, quantize "
        "weights or KV, or raise the parallel degrees",
    )]


# ---------------------------------------------------------------------------
# 12. serving-role program-set audit
# ---------------------------------------------------------------------------

#: program tags a role-restricted app must NOT ship (dead weight: compiled,
#: loaded into HBM, never dispatched by that role's engine)
_ROLE_FORBIDDEN_TAGS: Dict[str, Tuple[str, ...]] = {
    "decode": (
        "context_encoding_model",   # decode admits KV imports, never prefills
        "prefix_prefill_model",
        "mixed_model",              # mixed packs prefill chunks — same dead CTE
    ),
    "prefill": (
        "tkg_multistep",            # prefill emits ONE token then hands off
        "tkg_device_loop",
        "mixed_model",
    ),
}


def check_program_set(art: ProgramArtifacts) -> List[Finding]:
    """A role-restricted app (``TpuConfig(role="prefill"|"decode")``) must
    ship ONLY its role's program set. Disaggregation's perf story rests on
    the specialization: a decode replica that still compiles the CTE bucket
    ladder pays its compile time, its HBM residency, and its warmup for
    programs the decode engine can never dispatch — and symmetrically for
    multi-step/device-loop TKG programs on a prefill replica. config.py
    refuses the obvious combinations at build time; this checker audits the
    COMPILED reality (what iter_programs actually yields), so a hand-built
    or deserialized app cannot smuggle dead submodels past the role."""
    role = getattr(art.tc, "role", "unified")
    forbidden = _ROLE_FORBIDDEN_TAGS.get(role, ())
    if art.tag not in forbidden:
        return []
    # one finding per (submodel, bucket) program: each is a separately
    # compiled + resident executable, so per-program reporting sizes the
    # waste honestly
    return [art.finding(
        "program_set",
        f"role={role!r} app ships submodel {art.tag!r} — a "
        f"{'decode' if role == 'decode' else 'prefill'}-role engine never "
        f"dispatches it, so the program is dead weight (compile time + HBM "
        f"residency); rebuild with role='unified' or drop the submodel "
        f"flags that compile it",
    )]


#: name -> checker; the auditor runs these in order
CHECKERS: Dict[str, Callable[[ProgramArtifacts], List[Finding]]] = {
    "donation": check_donation,
    "collectives": check_collectives,
    "dtype_drift": check_dtype_drift,
    "baked_constants": check_baked_constants,
    "required_strategies": check_required_strategies,
    "kv_layout": check_kv_layout,
    "mixed_program": check_mixed_program,
    "device_loop": check_device_loop,
    "lora_sharding": check_lora_sharding,
    "quantized_dtype": check_quantized_dtype,
    "hbm_fit": check_hbm_fit,
    "program_set": check_program_set,
}
