"""Mixture-of-Experts ops — router + expert MLPs, expert-parallel over the mesh.

Reference: modules/moe_v2.py:23-132 assembles RouterTopK + ExpertMLPsV2 +
SharedExperts into an MoE wrapper, with TPxEP process groups (:135-161) and
NKI blockwise-matmul kernels. TPU-native the same structure is:

  - **Router**: one replicated linear -> scoring (softmax / sigmoid /
    grouped-top-k for deepseek-V3) -> top-k -> (optional) renormalize, exactly
    HF's semantics so logits match the CPU golden.
  - **Experts, three forms of one computation** (same operands, float32
    accumulation and combine weights; :func:`expert_form` chooses among
    them per compiled program, from static shapes, at trace time):
      * **sorted**: the (row, expert) pairs are sorted by expert and run
        through ``jax.lax.ragged_dot`` — XLA's grouped matmul, the MXU-native
        equivalent of the reference's blockwise NKI expert kernels
        (ExpertMLPsV2 block dispatch). FLOPs scale with ``T x top_k``, not
        with ``T x num_experts``; at 128-expert/top-8 scale that is 16x fewer
        expert FLOPs than the dense form. Static shapes throughout: the sort,
        the group sizes, and the combine are all fixed-(T*K) arrays (every
        sorted pair goes through the grouped matmul, routed here or not), so
        the path jits/scans cleanly.
      * **dense**: every held expert runs on every row with a zero combine
        weight where the router did not pick it. No sort, no gather/scatter,
        and the einsums stream the weights at the HBM rate; the form of a
        chip's SHARE of few held experts once every one of them expects a row.
      * **compact**: the dense form's einsums over SLOTS instead of rows. Of
        the router's pairs only those whose expert is held are live; each is
        given a slot of its expert (a table of row ids, ``Ce`` slots an
        expert, from a running count per held expert: no sort), the slots'
        rows are gathered, multiplied, and each slot's weighted output goes
        back to its row by one more matmul. ``Ce`` is static: twice the
        expected load and no fewer than the chip's ridge, under which slot
        rows are free; an expert with more pairs than slots has the slot
        matmuls run again on its next ``Ce`` pairs (a loop whose trip count
        follows the largest load), so no pair is dropped. The form of a
        share's PREFILL: past twice the ridge the dense einsums are
        compute-bound on pairs the router never made.
  - **Parallelism**: three regimes over the (ep, tp) mesh axes (parallel/mesh
    AXIS_MP = the full model-parallel world):
      * full-EP (``ep=True``, default when the world divides the expert
        count): the expert dim is sharded over the whole (ep, tp) world.
      * expert-internal TP (``ep=False``): the expert intermediate dim is
        sharded over the world (the reference's moe_tp_degree).
      * hybrid TPxEP (``hybrid_ep=True``, from ``moe_ep_degree`` x
        ``moe_tp_degree``): experts shard over the dedicated ``ep`` mesh axis
        while each expert's intermediate shards over ``tp`` — the reference's
        moe_v2.py:135-161 TPxEP process-group factorization. Attention and
        dense layers keep sharding over the full world via AXIS_MP.
    A sharded layer is always sorted, under ``shard_map`` (GSPMD cannot
    partition a ragged_dot over its group dim); each shard computes its local
    experts / intermediate slice and one psum over (ep, tp) produces the
    combined output — the reference's EP dispatch AR/RS collectives
    (attention_base.py:179 EPDispatchOption).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from nxdi_tpu.parallel.mesh import AXIS_EP, AXIS_EPX, AXIS_MP, AXIS_TP


#: the three forms of the local expert computation (expert_form)
EXPERT_FORMS = ("dense", "sorted", "compact")

# Expert-form trace: moe_block appends the form each traced expert layer took.
# The choice is STATIC per program, so recording at trace time is exact;
# model_wrapper snapshots it per (submodel, bucket) beside the attention
# strategies (ops/attention_select.py _STRATEGY_TRACE) and counts it into the registry.
_FORM_TRACE: list = []


@dataclass(frozen=True)
class MoEArch:
    """Static MoE architecture description (hashable; part of DecoderArch)."""

    num_experts: int
    top_k: int
    intermediate_size: int  # per-expert intermediate
    hidden_act: str = "silu"
    norm_topk_prob: bool = True  # renormalize top-k weights (mixtral: always)
    # expert-parallel over the full (ep, tp) world (family builder sets this
    # when the world divides E); False -> expert-internal TP on the
    # intermediate dim; hybrid_ep -> experts over the ep axis, intermediate
    # over tp (reference: moe_ep_degree x moe_tp_degree, config.py:603)
    ep: bool = False
    hybrid_ep: bool = False
    # per-phase hybrid TPxEP (reference: HybridShardingConfig config.py:1060 +
    # moe_v2.py:135-161 per-phase process groups): prefill programs compile
    # TP-heavy (experts over ep, intermediate over epx x tp), decode programs
    # EP-heavy (experts over ep x epx, intermediate over tp). ``phase`` is a
    # per-SUBMODEL arch override (the TKG/speculation wrappers flip it to
    # "decode"); expert weights are duplicated per regime ("experts_tkg"),
    # mirroring the reference's preshard-hook duplication.
    per_phase_hybrid: bool = False
    phase: str = "prefill"
    # None: the layer chooses its form per program (expert_form). One of
    # EXPERT_FORMS pins it — what a TEST sets to put the forms side by side;
    # no family builder and no option sets it
    dispatch: Optional[str] = None
    # shared (always-on) experts, qwen2-moe/llama4 style
    shared_expert_intermediate_size: Optional[int] = None
    shared_expert_gated: bool = False  # sigmoid(gate(x)) scaling on shared out
    # gpt-oss variants (reference: models/gpt_oss/modeling_gpt_oss.py): router
    # takes top-k of LOGITS then softmaxes the selected values; experts carry
    # biases and use the clamped glu  (up+1) * gate*sigmoid(alpha*gate)
    topk_softmax: bool = False
    # llama4 (reference: models/llama4/): top-k logits -> sigmoid scores that
    # scale the expert INPUT (not output); shared expert always added
    llama4_router: bool = False
    router_bias: bool = False
    expert_bias: bool = False
    gptoss_glu: bool = False
    glu_limit: Optional[float] = None
    glu_alpha: float = 1.702
    # deepseek-V3 routing (reference contrib DeepSeek-V3; HF DeepseekV3TopkRouter):
    # sigmoid scores (+ optional learned correction bias used ONLY for
    # selection), grouped top-k over n_group groups keeping topk_group, final
    # weights from the UNCORRECTED sigmoid scores, scaled by routed_scaling
    sigmoid_routing: bool = False
    # phimoe (Phi-3.5-MoE) sparsemixer routing (HF sparsemixer, eval path):
    # expert k's weight comes from a softmax over scores THRESHOLD-masked at
    # (max - s)/clamp(|s|, min=max) > 2*jitter_eps, with the top-1 expert
    # masked out before selecting the second
    sparsemixer: bool = False
    router_jitter: float = 0.01
    n_group: Optional[int] = None
    topk_group: Optional[int] = None
    routed_scaling: float = 1.0
    correction_bias: bool = False
    # ONE CHIP'S SHARE of an expert-parallel layer, run without its exchange
    # (a chip of a decode pool that shares each layer by experts): the router
    # keeps its ``num_experts`` outputs and its top k, the parameter tree
    # holds ``held_experts`` of them, ``[first_held, first_held + held)``, and
    # the layer returns the partial sum of its own experts plus the shared
    # expert. None = every expert is held (the whole layer).
    held_experts: Optional[int] = None
    first_held: int = 0
    # rows a bf16 matmul needs before the chip this arch was built for is
    # compute-bound on it (analysis/costs.py ``ChipSpec.ridge_rows``: 240.5 on
    # a v5e), resolved ONCE from the TpuConfig where a share's arch is built:
    # what sizes the compact form's slots and puts its crossing
    # (compact_capacity, expert_form rule (c)), and what tells a share that
    # its dense einsums wait on its weights (expert_form). None = built for no
    # chip (a bare MoEArch): the rule never says compact and keeps rule (b)
    ridge_rows: Optional[float] = None

    def __post_init__(self):
        if self.dispatch not in (None,) + EXPERT_FORMS:
            raise ValueError(f"dispatch pins one of {EXPERT_FORMS} or is None, got {self.dispatch!r}")
        if self.held_experts is None:
            return
        if not 0 < self.held_experts <= self.num_experts - self.first_held or self.first_held < 0:
            raise ValueError(
                f"held experts [{self.first_held}, {self.first_held + self.held_experts}) "
                f"do not lie among the router's {self.num_experts}"
            )
        if self.ep or self.hybrid_ep:
            raise ValueError(
                "a share of an expert layer (held_experts) is what ONE chip of an "
                "expert-parallel pool holds; it does not combine with an expert mesh axis"
            )

    @property
    def experts_here(self) -> int:
        """Experts in the parameter tree."""
        return self.num_experts if self.held_experts is None else self.held_experts


def ep_policy(tp_degree: int, num_experts: int) -> bool:
    """Shared EP-vs-TP decision for family builders: expert parallelism when
    the tp world divides the expert count."""
    return tp_degree > 1 and num_experts % tp_degree == 0


def moe_parallel_fields(tc, num_experts: int) -> Dict[str, Any]:
    """MoEArch constructor kwargs for the parallel knobs, derived from the
    :class:`TpuConfig` — shared by every MoE family builder."""
    hsc = getattr(tc, "hybrid_sharding_config", None)
    if hsc is not None:
        if num_experts % hsc.moe_tkg_ep_degree:
            raise ValueError(
                f"moe_tkg_ep_degree ({hsc.moe_tkg_ep_degree}) must divide the "
                f"expert count ({num_experts})"
            )
        return {"ep": False, "hybrid_ep": True, "per_phase_hybrid": True}
    hybrid = bool(getattr(tc, "moe_ep_degree", None) and tc.moe_ep_degree > 1)
    if hybrid and num_experts % tc.moe_ep_degree != 0:
        raise ValueError(
            f"moe_ep_degree ({tc.moe_ep_degree}) must divide the expert count "
            f"({num_experts})"
        )
    return {
        "ep": (not hybrid) and ep_policy(tc.tp_degree, num_experts),
        "hybrid_ep": hybrid,
    }


def convert_hf_experts(get, cast, num_experts: int, router_key: str, expert_fmt) -> Dict[str, Any]:
    """Stack per-expert HF weights into the (E, in, out) layout ops/moe.py
    consumes. ``expert_fmt(j, proj)`` yields the HF key for expert j's
    gate/up/down projection."""
    import numpy as np

    gate = np.stack([get(expert_fmt(j, "gate")).T for j in range(num_experts)])
    up = np.stack([get(expert_fmt(j, "up")).T for j in range(num_experts)])
    down = np.stack([get(expert_fmt(j, "down")).T for j in range(num_experts)])
    return {
        "router": {"w": cast(get(router_key).T)},
        "experts": {
            "gate_proj": {"w": cast(gate)},
            "up_proj": {"w": cast(up)},
            "down_proj": {"w": cast(down)},
        },
    }


def _expert_dim_axes(moe: MoEArch, phase: Optional[str] = None) -> Tuple[str, ...]:
    """Mesh axes sharding the expert dim (for specs and shard_map offsets).
    ``phase`` overrides ``moe.phase`` (spec builders emit both regimes)."""
    if moe.hybrid_ep:
        if moe.per_phase_hybrid and (phase or moe.phase) == "decode":
            return (AXIS_EP, AXIS_EPX)
        return (AXIS_EP,)
    if moe.ep:
        return AXIS_MP
    return ()


def _inter_dim_axes(moe: MoEArch, phase: Optional[str] = None) -> Tuple[str, ...]:
    """Mesh axes sharding the expert intermediate dim."""
    if moe.hybrid_ep:
        if moe.per_phase_hybrid and (phase or moe.phase) == "decode":
            return (AXIS_TP,)
        return (AXIS_EPX, AXIS_TP)
    if moe.ep:
        return ()
    return AXIS_MP


def _axes_entry(axes: Tuple[str, ...]):
    """PartitionSpec entry for an axes tuple ('' -> None)."""
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def expert_parallel_specs(moe: MoEArch) -> Dict[str, Any]:
    """PartitionSpecs for one layer's MoE params (pre-layer-stacking).

    Expert dim over :func:`_expert_dim_axes`, intermediate dim over
    :func:`_inter_dim_axes` (reference: moe_ep_degree vs moe_tp_degree,
    config.py:603). In hybrid mode weights are 2-D sharded (experts x
    intermediate)."""
    def expert_spec_for(phase):
        e = _axes_entry(_expert_dim_axes(moe, phase))
        i = _axes_entry(_inter_dim_axes(moe, phase))
        spec = {
            "gate_proj": {"w": P(e, None, i)},
            "up_proj": {"w": P(e, None, i)},
            "down_proj": {"w": P(e, i, None)},
        }
        if moe.expert_bias:
            spec["gate_proj"]["b"] = P(e, i)
            spec["up_proj"]["b"] = P(e, i)
            spec["down_proj"]["b"] = P(e, None)
        return spec

    specs: Dict[str, Any] = {
        "router": {"w": P()},
        "experts": expert_spec_for("prefill"),
    }
    if moe.per_phase_hybrid:
        # duplicated decode-regime copy (reference: mlp_op_tkg duplication in
        # the hybrid preshard hook)
        specs["experts_tkg"] = expert_spec_for("decode")
    if moe.router_bias:
        specs["router"]["b"] = P()
    if moe.correction_bias:
        specs["router"]["e_bias"] = P()
    if moe.shared_expert_intermediate_size:
        specs["shared_expert"] = {
            "gate_proj": {"w": P(None, AXIS_MP)},
            "up_proj": {"w": P(None, AXIS_MP)},
            "down_proj": {"w": P(AXIS_MP, None)},
        }
        if moe.shared_expert_gated:
            specs["shared_expert_gate"] = {"w": P()}
    return specs


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def route_topk(
    router_logits: jax.Array, moe: MoEArch, p_router: Optional[Dict[str, Any]] = None
) -> Tuple[jax.Array, jax.Array]:
    """Router logits (T, E) -> (weights (T, K) f32, expert ids (T, K) i32).

    Covers the HF routing family zoo: full-softmax top-k (mixtral/qwen3moe,
    reference RouterTopK moe_v2.py:23), top-k-then-softmax (gpt-oss), sigmoid
    top-k on the INPUT scale (llama4), and deepseek-V3 sigmoid grouped top-k
    with selection-only correction bias."""
    logits = router_logits.astype(jnp.float32)
    if moe.sparsemixer:
        # HF phimoe sparsemixer, inference path (top-2 only)
        assert moe.top_k == 2, "sparsemixer routing is top-2"

        def pick(scores):
            mx = jnp.max(scores, axis=-1, keepdims=True)
            idx = jnp.argmax(scores, axis=-1, keepdims=True)
            factor = jnp.maximum(jnp.abs(scores), mx)
            drop = (mx - scores) / factor > 2.0 * moe.router_jitter
            gates = jax.nn.softmax(jnp.where(drop, -jnp.inf, scores), axis=-1)
            w = jnp.take_along_axis(gates, idx, axis=-1)
            return w, idx

        w1, i1 = pick(logits)
        masked = jnp.where(
            jax.nn.one_hot(i1[:, 0], logits.shape[-1], dtype=bool), -jnp.inf, logits
        )
        # the second threshold mask uses the ORIGINAL |scores| clamp floor
        mx2 = jnp.max(masked, axis=-1, keepdims=True)
        i2 = jnp.argmax(masked, axis=-1, keepdims=True)
        factor2 = jnp.maximum(jnp.abs(logits), mx2)
        drop2 = (mx2 - logits) / factor2 > 2.0 * moe.router_jitter
        gates2 = jax.nn.softmax(jnp.where(drop2, -jnp.inf, masked), axis=-1)
        w2 = jnp.take_along_axis(gates2, i2, axis=-1)
        return (
            jnp.concatenate([w1, w2], axis=-1),
            jnp.concatenate([i1, i2], axis=-1).astype(jnp.int32),
        )
    if moe.sigmoid_routing or moe.routed_scaling != 1.0 or (moe.n_group or 0) > 1:
        # deepseek lineage. V3 (sigmoid_routing): sigmoid scores, selection
        # over bias-corrected scores, group metric = sum of top-2 members.
        # V2 (softmax scoring): softmax scores, no correction bias, group
        # metric = max member (HF DeepseekV2 MoEGate). Both: weights from the
        # raw scores, optional renorm, * routed_scaling_factor.
        if moe.sigmoid_routing:
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        select = scores
        if moe.correction_bias:
            select = scores + p_router["e_bias"].astype(jnp.float32)
        if moe.n_group and moe.n_group > 1:
            T = logits.shape[0]
            E, G = moe.num_experts, moe.n_group
            grouped = select.reshape(T, G, E // G)
            if moe.sigmoid_routing:
                top2, _ = jax.lax.top_k(grouped, min(2, E // G))
                group_scores = jnp.sum(top2, axis=-1)
            else:
                group_scores = jnp.max(grouped, axis=-1)
            _, group_idx = jax.lax.top_k(group_scores, moe.topk_group)
            group_mask = jnp.sum(
                jax.nn.one_hot(group_idx, G, dtype=jnp.float32), axis=-2
            )  # (T, G)
            member_mask = jnp.repeat(group_mask, E // G, axis=-1)
            select = jnp.where(member_mask > 0, select, -jnp.inf)
        _, top_idx = jax.lax.top_k(select, moe.top_k)
        # weights come from the UNCORRECTED scores
        top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
        if moe.norm_topk_prob:
            top_vals = top_vals / (jnp.sum(top_vals, axis=-1, keepdims=True) + 1e-20)
        top_vals = top_vals * moe.routed_scaling
        return top_vals, top_idx
    if moe.llama4_router:
        top_vals, top_idx = jax.lax.top_k(logits, moe.top_k)
        return jax.nn.sigmoid(top_vals), top_idx
    if moe.topk_softmax:
        # gpt-oss: top-k on raw logits, softmax over the k selected values
        top_vals, top_idx = jax.lax.top_k(logits, moe.top_k)
        return jax.nn.softmax(top_vals, axis=-1), top_idx
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, moe.top_k)  # (T, K)
    if moe.norm_topk_prob:
        top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    return top_vals, top_idx


def _dense_combine_weights(top_vals: jax.Array, top_idx: jax.Array, moe: MoEArch) -> jax.Array:
    """Top-k (weights, ids) (T, K) -> combine weights (T, E), zero where the
    router did not pick the expert (the dense form's)."""
    return jnp.sum(
        jax.nn.one_hot(top_idx, moe.num_experts, dtype=top_vals.dtype)
        * top_vals[..., None],
        axis=-2,
    )  # (T, E)


# ---------------------------------------------------------------------------
# Expert compute — the sorted (ragged_dot) and the dense form, and what chooses
# ---------------------------------------------------------------------------


def _no_exchange(moe: MoEArch, mesh) -> bool:
    """The layer computes on this chip alone: no mesh in scope, or a share on
    a one-chip model-parallel world (nothing to exchange)."""
    return (
        mesh is None or mesh.empty or not set(AXIS_MP).issubset(mesh.axis_names)
        or (moe.held_experts is not None and all(mesh.shape[a] == 1 for a in AXIS_MP))
    )


def compact_capacity(moe: MoEArch, rows: int) -> int:
    """Slots an expert in the compact form at ``rows`` rows: twice the load a
    held expert expects, ``2 * rows * top_k / num_experts``, and no fewer than
    the chip's ridge (``MoEArch.ridge_rows``: under it the slots' einsums wait
    on the weights, so its rows cost nothing), in whole bf16 sublane tiles of
    16 rows: 256 on a v5e from 512 to 4096 rows of top 8 over 256. A function
    of the shapes and the chip's peaks alone; a load past it costs another trip
    of the slot matmuls (``_compact_expert_ffn``), never a pair."""
    want = max(2 * rows * moe.top_k / moe.num_experts, moe.ridge_rows or 0.0)
    return math.ceil(want / 16) * 16


def expert_form(moe: MoEArch, rows: int) -> str:
    """The form (one of ``EXPERT_FORMS``) of the local expert computation in a
    program that hands the layer ``rows`` rows (B x S: a model's
    token-generation program and each of its prefill buckets choose apart),
    under the mesh in scope.

    Sorted where the layer exchanges rows with other chips, or where

    (a) ``rows * top_k < num_experts``: a held expert expects less than one
        row, so the sorted form reads only the experts that were hit (one row
        over 320 experts) where the other two stream all the held weights.

    Otherwise, a share built for a chip (``held_experts`` and ``ridge_rows``
    set) takes

    (c) the compact form where its slots are at most half its row-experts:
        ``2 * compact_capacity(moe, rows) <= rows``. The slots are no fewer
        than the chip's ridge (240.5 rows on a v5e: 256 slots), so there this
        is ``rows >= 512`` (and ``num_experts >= 4 * top_k``): past twice the
        ridge the dense einsums are compute-bound on pairs the router never
        made, and the slots' einsums stream the same weights over half the
        rows or fewer. One layer alone on the chip, 16 of 256 held, top 8,
        ms dense / compact at 256 | 512 | 1024 | 4096 rows: hidden 7680
        **3.22** / 3.34 | 5.46 / **3.29** | 10.46 / **3.79** | 39.99 /
        **6.34**; hidden 4096 **2.04** / 2.12 | 3.23 / **2.18** | 6.08 /
        **2.43** | 22.61 / **3.73** (PERF.md section 6, PR 36);

    and the dense form under that. The dense einsums do ``rows`` operations a
    weight byte whatever the held count, so up to the ridge they wait on the
    held weights, which the sorted form streams too once (a) fails, and from
    the ridge to twice it they cost at most twice that stream; the sorted
    form's grouped matmul reads them at 30-37 % of the HBM rate at 3-6 rows
    an expert. One Solar-Open2 layer alone on the chip, 40 of 320 held, top
    8, ms dense / sorted / compact at 128 | 256 | 512 | 1024 rows: **2.43** /
    5.15 / 2.98 | **2.81** / 5.56 / 3.11 | 4.72 / 6.03 / **3.40** | 9.48 /
    6.96 / **3.61**; 24 and 32 held read alike (PERF.md section 6, PR 42).

    A layer built for no chip (a whole published layer: 8 experts top 2, 60
    top 4, 128 top 8, 256 top 8; or a bare ``MoEArch``) knows no ridge and
    keeps, beside (a),

    (b) ``experts_here <= 2 * top_k`` for the dense form, else sorted: the
        dense form multiplies ``rows * experts_here`` row-experts where the
        sorted one sends ``rows * top_k`` sorted pairs through the grouped
        matmul (PERF.md section 6, PR 31).

    Under an expert or intermediate mesh axis the sorted form inside
    ``shard_map`` stays (its collectives are what analysis/budget.py budgets)."""
    if moe.dispatch is not None:
        return moe.dispatch
    if not _no_exchange(moe, jax.sharding.get_abstract_mesh()):
        return "sorted"
    if rows * moe.top_k < moe.num_experts:
        return "sorted"
    if moe.held_experts is None or moe.ridge_rows is None:
        return "dense" if moe.experts_here <= 2 * moe.top_k else "sorted"
    return "compact" if 2 * compact_capacity(moe, rows) <= rows else "dense"


def _expert_act(moe: MoEArch, gate: jax.Array, up: jax.Array) -> jax.Array:
    from nxdi_tpu.models.base import ACT_FNS

    if moe.gptoss_glu:
        if moe.glu_limit is not None:
            gate = jnp.minimum(gate, moe.glu_limit)
            up = jnp.clip(up, -moe.glu_limit, moe.glu_limit)
        return (up + 1.0) * (gate * jax.nn.sigmoid(gate * moe.glu_alpha))
    return ACT_FNS[moe.hidden_act](gate) * up


def _grouped_matmul(xs, w, group_sizes, layer, precision):
    """``ragged_dot`` of sorted rows against the experts' weights. ``w`` is
    one layer's ``(E, in, out)`` (``layer`` None) or a segment's layer-stacked
    ``(L, E, in, out)``: the stack is then handed over WHOLE, as L*E groups of
    which only layer ``layer``'s have rows. The TPU's grouped matmul is a
    kernel and takes its operand as a buffer: given a layer's slice of the
    stack it has the slice materialised first (0.5 GiB a matrix at 16 experts
    of 7680 x 2048), where the whole stack is read in place and the empty
    groups cost no tile."""
    if layer is not None:
        L, E = w.shape[:2]
        w = w.reshape((L * E,) + w.shape[2:])
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), group_sizes.dtype), group_sizes, (layer * E,)
        )
    return jax.lax.ragged_dot(xs, w, group_sizes, precision=precision)


def _sparse_expert_ffn(
    moe: MoEArch,
    ew: Dict[str, Any],
    xt: jax.Array,  # (T, H) local tokens
    weights: jax.Array,  # (T, K) f32 combine weights
    idx: jax.Array,  # (T, K) i32 expert ids
    e_lo,  # scalar: first expert id held locally
    e_count: int,  # number of experts held locally
    down_bias_on=1.0,  # 0/1 gate so replicated down biases aren't double-psummed
    layer=None,  # the weights in ``ew`` are layer-stacked; this layer's index
) -> jax.Array:
    """Grouped-matmul expert FFN over the locally-held expert/intermediate
    shard. Returns the PARTIAL combined output (T, H) — callers psum over the
    (ep, tp) axes when sharded.

    The ragged_dot grouped matmul wants rows sorted by group; rows routed to
    non-local experts sort to a tail past ``sum(group_sizes)`` whose output is
    unspecified — they are dropped at the combine and never contribute."""
    T, H = xt.shape
    K = moe.top_k
    N = T * K
    # float32 inputs multiply exactly only at HIGHEST; bf16 x bf16 products
    # are exact in the float32 accumulator at any precision, and the TPU's
    # grouped matmul (a Mosaic kernel) refuses HIGHEST on bf16 operands
    hp = jax.lax.Precision.HIGHEST if xt.dtype == jnp.float32 else None

    flat_e = idx.reshape(N)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    local_e = flat_e - e_lo
    in_range = (local_e >= 0) & (local_e < e_count)
    sort_key = jnp.where(in_range, local_e, e_count).astype(jnp.int32)
    order = jnp.argsort(sort_key, stable=True)
    se = sort_key[order]  # sorted local expert ids (tail = e_count)
    st = flat_t[order]  # token row per sorted slot
    comb = jnp.where(in_range, weights.reshape(N), 0.0)[order]  # f32

    xs = jnp.take(xt, st, axis=0)  # (N, H)
    if moe.llama4_router:
        # llama4 scales the expert INPUT by the sigmoid score; combine weight 1
        xs = xs * comb[:, None].astype(xs.dtype)
        comb = jnp.where(comb > 0, 1.0, 0.0)
    group_sizes = jnp.bincount(se, length=e_count).astype(jnp.int32)

    se_c = jnp.minimum(se, e_count - 1)  # clipped for bias gathers
    gate = _grouped_matmul(xs, ew["gate_proj"]["w"], group_sizes, layer, hp)
    up = _grouped_matmul(xs, ew["up_proj"]["w"], group_sizes, layer, hp)
    if moe.expert_bias:
        gate = gate + ew["gate_proj"]["b"][se_c]
        up = up + ew["up_proj"]["b"][se_c]
    inner = _expert_act(moe, gate, up)
    rows = _grouped_matmul(inner, ew["down_proj"]["w"], group_sizes, layer, hp)
    if moe.expert_bias:
        rows = rows + (ew["down_proj"]["b"][se_c] * down_bias_on).astype(rows.dtype)

    # the tail's rows are whatever the kernel left there (0 x NaN is NaN)
    rows = jnp.where((se < e_count)[:, None], rows * comb[:, None].astype(rows.dtype), 0)
    # un-sort back to (T, K) slots, then reduce over K — deterministic combine
    unsorted = jnp.zeros((N, H), rows.dtype).at[order].set(rows)
    return jnp.sum(unsorted.reshape(T, K, H), axis=1)


def _compact_expert_ffn(
    moe: MoEArch,
    p_experts: Dict[str, Any],  # the held experts' leaves, as the dense form reads them
    xt: jax.Array,  # (T, H)
    top_vals: jax.Array,  # (T, K) f32 combine weights
    top_idx: jax.Array,  # (T, K) i32 expert ids
    stacked=None,  # (gate, up, down, layer): the weights layer-stacked, and this layer's index
    live_rows=None,  # (T,) bool: False on a row whose pairs get no slot (moe_block ``last_real``)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The held experts over SLOTS: ``(T, H)`` partial output, the live pairs
    (int32: held expert and live row) and the slot rows multiplied for them
    (int32: ``experts_here * Ce * trips``).

    A row's top k are k different experts, so a held expert sees a row at most
    once: ``live`` (T, E_here) marks its rows and a running count down the
    rows numbers them. Trip ``r`` serves the pairs numbered ``[r * Ce, (r + 1)
    * Ce)`` of every expert: slot ``j``'s row is the first whose count reaches
    ``r * Ce + j + 1`` (the rows with a smaller count, counted: no sort, no
    scatter of ids), an empty slot reads row T - 1 and goes nowhere. The
    slots' rows go through the dense form's three einsums, ``Ce`` rows an
    expert in place of T, and the slots' outputs go back to their rows by one
    more matmul, against ``place`` (T, E_here, Ce): a pair's combine weight at
    its own slot, zeros elsewhere; operands in the model's dtype as the dense
    form's combine, accumulated in float32 across slots and trips.
    ``trips = ceil(largest load / Ce)`` is data: one where the router spreads
    its pairs, none where no pair is held, and a hot expert costs the held
    weights streamed again, not a dropped pair.

    The trips are a loop, and what a loop reads crosses its boundary as a
    buffer: inside the layer scan a layer's slice of the scan's xs would be
    materialised for it (1.4 GiB of ``temp`` at 16 experts of 7680 x 2048), so
    there the weights come ``stacked``, WHOLE as for the grouped matmul, and
    each einsum slices its layer inside the loop, read in place."""
    from nxdi_tpu.ops.quantization import materialize_weight as mat_w

    if stacked is None:
        layer = None

        def mat(k, _):
            return mat_w(p_experts[k], xt.dtype)
    else:
        *stack, layer = stacked
        stack = dict(zip(("gate_proj", "up_proj", "down_proj"), stack))

        def mat(k, layer):
            return jax.lax.dynamic_index_in_dim(stack[k], layer, 0, keepdims=False).astype(xt.dtype)

    T, H = xt.shape
    lo, n_here = moe.first_held, moe.experts_here
    Ce = compact_capacity(moe, T)
    hit = (top_idx - lo)[:, :, None] == jnp.arange(n_here, dtype=top_idx.dtype)  # (T, K, E_here)
    if live_rows is not None:
        hit = hit & live_rows[:, None, None]
    live = jnp.any(hit, axis=1)
    weight = jnp.sum(jnp.where(hit, top_vals[:, :, None], 0.0), axis=1)  # (T, E_here) f32
    count = jnp.cumsum(live.astype(jnp.int32), axis=0)  # (T, E_here): pairs up to this row
    trips = (jnp.max(count[-1]) + Ce - 1) // Ce

    def trip(r, out):
        # the layer's index tied to the trip: a slice that did not depend on
        # the trip would be hoisted out of the loop, and materialised there
        li = None if layer is None else jax.lax.optimization_barrier((layer, r))[0]
        want = r * Ce + 1 + jnp.arange(Ce, dtype=jnp.int32)
        rows = jnp.sum(count[:, :, None] < want, axis=0, dtype=jnp.int32)  # (E_here, Ce)
        rows = jnp.minimum(rows, T - 1)
        xs = jnp.take(xt, rows, axis=0)  # (E_here, Ce, H)
        # where each row's pair sits among this trip's slots, with its weight:
        # (T, E_here, Ce), zero but at a live pair's own slot
        place = jnp.where(live[:, :, None] & (count[:, :, None] == want), weight[:, :, None], 0.0)
        if moe.llama4_router:
            # llama4 scales the expert INPUT by the sigmoid score; combine weight 1
            xs = xs * jnp.sum(place, axis=0)[:, :, None].astype(xs.dtype)
            place = (place > 0).astype(place.dtype)
        gate = jnp.einsum("ech,ehi->eci", xs, mat("gate_proj", li))
        up = jnp.einsum("ech,ehi->eci", xs, mat("up_proj", li))
        if moe.expert_bias:
            gate = gate + p_experts["gate_proj"]["b"][:, None, :]
            up = up + p_experts["up_proj"]["b"][:, None, :]
        inner = _expert_act(moe, gate, up)  # (E_here, Ce, I)
        ys = jnp.einsum("eci,eih->ech", inner, mat("down_proj", li))
        if moe.expert_bias:
            ys = ys + p_experts["down_proj"]["b"][:, None, :]
        # each slot's output back to its row: a matmul with ``place`` (XLA's
        # scatter-add of 1024 rows of 7680 read 3.5 ms on the chip, this 0.2)
        return out + jnp.einsum(
            "tec,ech->th", place.astype(ys.dtype), ys, preferred_element_type=jnp.float32
        )

    out = jax.lax.fori_loop(0, trips, trip, jnp.zeros((T, H), jnp.float32))
    return out.astype(xt.dtype), jnp.sum(count[-1]), trips * (n_here * Ce)


def _strip_mp_axes(spec: P) -> P:
    """Drop ep/tp axes from an activation spec (tokens replicate over the
    model-parallel world inside the sparse shard_map; dp/cp stay sharded)."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        axes = tuple(a for a in (entry if isinstance(entry, (tuple, list)) else (entry,))
                     if a not in (AXIS_EP, AXIS_EPX, AXIS_TP))
        out.append(_axes_entry(axes))
    return P(*out)


def _sparse_moe(
    moe: MoEArch,
    experts: Dict[str, Any],  # dequantized expert weights (global view)
    x: jax.Array,  # (B, S, H)
    weights: jax.Array,  # (B, S, K) f32
    idx: jax.Array,  # (B, S, K) i32
    hidden_spec: P,
    layer=None,  # the weights are layer-stacked (L, E, ..); this layer's index
) -> jax.Array:
    """Dispatch the sparse expert FFN, sharded over the mesh when one is in
    scope. Token (dp/cp) axes stay data-parallel; expert/intermediate shards
    each compute a partial combined output and one psum over (ep, tp) merges
    them — the EP dispatch collective of the reference (moe_v2.py:135-161)."""
    e_axes = _expert_dim_axes(moe)
    i_axes = _inter_dim_axes(moe)
    mesh = jax.sharding.get_abstract_mesh()

    def local(ex, xb, wb, ib, layer=None):
        B, S, H = xb.shape
        if e_axes:
            e_count = ex["gate_proj"]["w"].shape[-3]
            e_lo = jax.lax.axis_index(e_axes) * e_count
        else:
            e_count = moe.experts_here
            e_lo = moe.first_held
        if i_axes:
            down_on = (jax.lax.axis_index(i_axes) == 0).astype(jnp.float32)
        else:
            down_on = 1.0
        out = _sparse_expert_ffn(
            moe, ex, xb.reshape(B * S, H), wb.reshape(B * S, -1),
            ib.reshape(B * S, -1), e_lo, e_count, down_on, layer,
        )
        out = jax.lax.psum(out, AXIS_MP)
        return out.reshape(B, S, H)

    if _no_exchange(moe, mesh):
        return _sparse_expert_ffn(
            moe,
            experts,
            x.reshape(-1, x.shape[-1]),
            weights.reshape(-1, moe.top_k),
            idx.reshape(-1, moe.top_k),
            moe.first_held,
            moe.experts_here,
            layer=layer,
        ).reshape(x.shape)

    tok_spec = _strip_mp_axes(hidden_spec)
    tok2 = P(tok_spec[0] if len(tok_spec) > 0 else None,
             tok_spec[1] if len(tok_spec) > 1 else None, None)
    e = _axes_entry(e_axes)
    i = _axes_entry(i_axes)
    stack = () if layer is None else (None,)  # the leading layer axis
    w_specs = {
        "gate_proj": {"w": P(*stack, e, None, i)},
        "up_proj": {"w": P(*stack, e, None, i)},
        "down_proj": {"w": P(*stack, e, i, None)},
    }
    if moe.expert_bias:
        w_specs["gate_proj"]["b"] = P(e, i)
        w_specs["up_proj"]["b"] = P(e, i)
        w_specs["down_proj"]["b"] = P(e, None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(w_specs, tok2, tok2, tok2) + (() if layer is None else (P(),)),
        out_specs=tok2,
        check_vma=False,
    )
    return fn(experts, x, weights, idx, *(() if layer is None else (layer,)))


def moe_block(
    arch, moe: MoEArch, p: Dict[str, Any], x: jax.Array, hidden_spec: Optional[P] = None,
    held_tally: Optional[list] = None, stacked_experts=None,
    last_real: Optional[jax.Array] = None,
) -> jax.Array:
    """MoE feed-forward: (B, S, H) -> (B, S, H).

    Param leaves: router.w (H, E); experts.{gate,up}_proj.w (E_here, H, I),
    experts.down_proj.w (E_here, I, H); optional shared_expert mlp. E is the
    router's width, E_here the experts this program holds (``MoEArch
    .held_experts``; all of them unless the layer is one chip's share).

    ``held_tally``: a list that gains this layer's ``(held, slot_rows)``:
    the count (int32 scalar) of (row, expert) pairs routed to a held expert —
    the step program's counter ``moe_held_pairs`` (models/base.py
    run_decoder_layers), taken from the router's top k, the same pairs in any
    form — and the slot rows the compact form multiplied for them (None in the
    other two forms; the compact form's pairs are those of its live rows).

    ``last_real`` (B,): the index of each batch row's last real token, handed
    over by a FRESH prefill alone (models/base.py ``decoder_layer``): there the
    rows behind it are bucket padding, and the compact form gives them no slot
    (and counts no pair of theirs): a prompt's padding is one token over and
    over, its rows route nearly alike, and where their experts are held they
    would be one expert's load several slots deep. The prefill's token comes
    from the last real row and its padding's cache rows lie where a later step
    writes before any reads; the rows' own output (all-position logits, a
    captured hidden state) is the shared expert's alone. The other forms
    compute every row, as ever; a program whose rows are all read (a
    speculation verify window, whose ``last_token_index`` is no length) hands
    over None.

    ``stacked_experts`` (the sorted and the compact form inside the layer
    scan): ``(gate, up, down, layer)``, the segment's layer-stacked expert
    weights ``(L, E_here, ..)`` kept OUT of the scan's xs, and this layer's
    index among them; ``p`` then has no ``experts.*.w`` (``_grouped_matmul``
    and ``_compact_expert_ffn`` say why). Under the dense form the weights
    ride the xs: an einsum reads a layer's slice in place.
    """
    from nxdi_tpu.ops.quantization import materialize_weight as mat_w

    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    lo, n_here = moe.first_held, moe.experts_here

    with jax.named_scope("moe.route"):
        router_logits = xt.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
        if moe.router_bias:
            router_logits = router_logits + p["router"]["b"].astype(jnp.float32)
        top_vals, top_idx = route_topk(router_logits, moe, p["router"])  # (T, K)
        held = slot_rows = None
        if held_tally is not None:
            held = jnp.sum((top_idx >= lo) & (top_idx < lo + n_here), dtype=jnp.int32)

    # per-phase hybrid: decode programs read the EP-heavy duplicated copy
    p_experts = p["experts"]
    if moe.per_phase_hybrid and moe.phase == "decode" and "experts_tkg" in p:
        p_experts = p["experts_tkg"]

    # weights held out of the scan's xs: the caller asked expert_form with this
    # program's rows and got no dense (models/base.py _extract_stacked_weights)
    form = expert_form(moe, B * S)
    assert stacked_experts is None or form != "dense", form
    _FORM_TRACE.append(form)
    if form == "sorted":
        layer = None
        if stacked_experts is not None:
            *stacked, layer = stacked_experts
            p_experts = {
                k: {**p_experts[k], "w": w}
                for k, w in zip(("gate_proj", "up_proj", "down_proj"), stacked)
            }
        experts = {
            "gate_proj": {"w": mat_w(p_experts["gate_proj"], x.dtype)},
            "up_proj": {"w": mat_w(p_experts["up_proj"], x.dtype)},
            "down_proj": {"w": mat_w(p_experts["down_proj"], x.dtype)},
        }
        if moe.expert_bias:
            for k in experts:
                experts[k]["b"] = p_experts[k]["b"]
        with jax.named_scope("moe.experts.sorted"):
            out = _sparse_moe(
                moe,
                experts,
                x,
                top_vals.reshape(B, S, moe.top_k),
                top_idx.reshape(B, S, moe.top_k),
                hidden_spec if hidden_spec is not None else P(),
                layer,
            ).reshape(B * S, H)
    elif form == "compact":
        with jax.named_scope("moe.experts.compact"):
            live_rows = None
            if last_real is not None:
                live_rows = (jnp.arange(S)[None, :] <= last_real[:, None]).reshape(B * S)
            out, held, slot_rows = _compact_expert_ffn(
                moe, p_experts, xt, top_vals, top_idx, stacked_experts, live_rows
            )
    else:
        with jax.named_scope("moe.experts.dense"):
            weights = _dense_combine_weights(top_vals, top_idx, moe).astype(x.dtype)  # (T, E)
            if moe.held_experts is not None:
                weights = weights[:, lo: lo + n_here]  # the held experts' columns
            # all held experts on all rows, combine contracted over E. mat_w
            # dequantizes low-bit expert weights in the einsum's operand read.
            gate = jnp.einsum("th,ehi->eti", xt, mat_w(p_experts["gate_proj"], x.dtype))
            up = jnp.einsum("th,ehi->eti", xt, mat_w(p_experts["up_proj"], x.dtype))
            if moe.llama4_router:
                # llama4 scales the expert INPUT by the sigmoid score. gate/up are
                # linear and bias-free on this path, so scaling their OUTPUTS before
                # the activation is identical (act(s*g(x)) where s*g(x) = g(s*x)) —
                # avoids materializing an (E, T, H) scaled-input tensor
                se = jnp.swapaxes(weights, 0, 1)[:, :, None].astype(gate.dtype)  # (E, T, 1)
                gate = gate * se
                up = up * se
            if moe.expert_bias:
                gate = gate + p_experts["gate_proj"]["b"][:, None, :]
                up = up + p_experts["up_proj"]["b"][:, None, :]
            inner = _expert_act(moe, gate, up)  # (E, T, I)
            expert_out = jnp.einsum("eti,eih->eth", inner, mat_w(p_experts["down_proj"], x.dtype))
            if moe.expert_bias:
                expert_out = expert_out + p_experts["down_proj"]["b"][:, None, :]
            if moe.llama4_router:
                out = jnp.sum(expert_out, axis=0)  # input already carries the score
            else:
                out = jnp.einsum("te,eth->th", weights, expert_out)  # psum over E under EP
    if held_tally is not None:
        held_tally.append((held, slot_rows))

    if moe.shared_expert_intermediate_size:
        from nxdi_tpu.models.base import ACT_FNS

        act = ACT_FNS[moe.hidden_act]
        sp = p["shared_expert"]
        with jax.named_scope("moe.shared"):
            shared = (
                act(xt @ mat_w(sp["gate_proj"], x.dtype)) * (xt @ mat_w(sp["up_proj"], x.dtype))
            ) @ mat_w(sp["down_proj"], x.dtype)
            if moe.shared_expert_gated:
                shared = jax.nn.sigmoid(
                    xt.astype(jnp.float32) @ p["shared_expert_gate"]["w"].astype(jnp.float32)
                ).astype(shared.dtype) * shared
            out = out + shared

    return out.reshape(B, S, H)


def duplicate_per_phase_experts(obj):
    """Mirror every MoE ``experts`` subtree as ``experts_tkg`` in a HOST param
    pytree (reference: ``duplicate_and_replace_prefixes`` in the hybrid
    preshard hook — the decode regime gets its own sharded copy). Host arrays
    are shared; ``device_put`` lays each copy out under its own spec."""
    if isinstance(obj, dict):
        out = {k: duplicate_per_phase_experts(v) for k, v in obj.items()}
        if "router" in out and "experts" in out and "experts_tkg" not in out:
            out["experts_tkg"] = out["experts"]
        return out
    if isinstance(obj, (list, tuple)):
        return type(obj)(duplicate_per_phase_experts(v) for v in obj)
    return obj


def moe_shape_struct(moe: MoEArch, hidden_size: int, num_layers: int, dtype) -> Dict[str, Any]:
    """ShapeDtypeStruct pytree for layer-stacked MoE params."""

    def s(*shape):
        return jax.ShapeDtypeStruct((num_layers,) + shape, dtype)

    # the router scores all E experts; the tree holds EH of them (a share)
    E, EH, H, I = moe.num_experts, moe.experts_here, hidden_size, moe.intermediate_size
    struct: Dict[str, Any] = {
        "router": {"w": s(H, E)},
        "experts": {
            "gate_proj": {"w": s(EH, H, I)},
            "up_proj": {"w": s(EH, H, I)},
            "down_proj": {"w": s(EH, I, H)},
        },
    }
    if moe.router_bias:
        struct["router"]["b"] = s(E)
    if moe.correction_bias:
        # f32 regardless of model dtype (selection-precision critical)
        struct["router"]["e_bias"] = jax.ShapeDtypeStruct((num_layers, E), jnp.float32)
    if moe.expert_bias:
        struct["experts"]["gate_proj"]["b"] = s(EH, I)
        struct["experts"]["up_proj"]["b"] = s(EH, I)
        struct["experts"]["down_proj"]["b"] = s(EH, H)
    if moe.per_phase_hybrid:
        import copy

        struct["experts_tkg"] = copy.deepcopy(struct["experts"])
    if moe.shared_expert_intermediate_size:
        SI = moe.shared_expert_intermediate_size
        struct["shared_expert"] = {
            "gate_proj": {"w": s(H, SI)},
            "up_proj": {"w": s(H, SI)},
            "down_proj": {"w": s(SI, H)},
        }
        if moe.shared_expert_gated:
            struct["shared_expert_gate"] = {"w": s(H, 1)}
    return struct
