"""Expected collective budget per compiled submodel program.

The budget is derived from what ``parallel/policy.py`` SHOULD produce for the
config — deliberately NOT from the ``ShardingPolicy`` object the wrapper
actually compiled with. If a policy regression sneaks sharding into a program
(the decode stream suddenly S-sharded, an extra replicated axis forcing
all-gathers), the budget stays put and the observed counts blow past it;
deriving the budget from the buggy policy itself would silently raise the
ceiling along with the bug.

Counts are *textual* upper bounds over the optimized HLO. The decoder layer
stack runs under ``lax.scan`` (one ``while`` body in HLO), so the per-layer
collectives appear once in text — budgets are therefore small constants per
feature, not multiples of ``num_layers``. Unscanned (unrolled) model families
can scale the body terms via ``layers_unrolled``.

Every contribution is recorded as an ``explain`` string so a budget failure
tells the reader what WAS allowed, not just that a number was exceeded.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from nxdi_tpu.analysis.hlo import COLLECTIVE_OPS


def _add(budget: Dict[str, int], explain: List[str], op: str, n: int, why: str) -> None:
    if n <= 0:
        return
    budget[op] += n
    explain.append(f"+{n} {op}: {why}")


def expected_collective_budget(
    tc, arch, wrapper
) -> Tuple[Dict[str, int], List[str]]:
    """Upper-bound collective counts for one submodel program.

    ``tc``: TpuConfig — the source of truth for which policy the submodel is
    *supposed* to run. ``arch``: the wrapper's DecoderArch (layer count, MoE).
    ``wrapper``: the ModelWrapper (decode-vs-prefill kind, speculation).
    """
    budget = {op: 0 for op in COLLECTIVE_OPS}
    explain: List[str] = []

    world = tc.tp_degree * getattr(tc, "pp_degree", 1)
    if world <= 1:
        explain.append("single-device mesh: every collective is unexplained")
        return budget, explain

    decode_like = wrapper.attend_to_cache and not wrapper.prefill_to_cache
    # which collective-inducing features the EXPECTED policy engages — owned
    # by parallel/policy.py so policy changes and budgets evolve together
    from nxdi_tpu.parallel.policy import expected_policy_features

    feats = expected_policy_features(tc, decode_like)
    # fused speculation runs TWO decoder stacks (draft + target) per program
    stacks = 2 if getattr(wrapper, "draft_arch", None) is not None else 1
    # unrolled families pay the body terms per layer; scanned (default) once
    body_scale = stacks * (
        arch.num_layers if getattr(wrapper, "layers_unrolled", False) else 1
    )

    if tc.tp_degree > 1:
        _add(budget, explain, "all-reduce", 2 * body_scale,
             "row-parallel attn-out + mlp-down psum (scanned layer body)")
        _add(budget, explain, "all-reduce", 2 * stacks,
             "final-norm / lm_head epilogue reduction")
        if tc.on_device_sampling_config is not None:
            _add(budget, explain, "all-gather", 3 * stacks,
                 "on-device sampling cross-shard top-k gather (values+indices)")
        if tc.output_logits:
            _add(budget, explain, "all-gather", 1,
                 "full-logits output gather (vocab-parallel lm_head)")

    if feats["sp"]:
        _add(budget, explain, "all-gather", 5 * body_scale,
             "SP: S-sharded stream gathered at QKV/MLP boundaries")
        _add(budget, explain, "reduce-scatter", 3 * body_scale,
             "SP: row-parallel psums become reduce-scatters")
        _add(budget, explain, "all-to-all", 2 * body_scale,
             "SP: partitioner resharding between S- and H-sharded views")
        _add(budget, explain, "all-reduce", 2 * body_scale,
             "SP: residual-stream reductions the partitioner keeps as psum")
    if feats["cp"]:
        _add(budget, explain, "all-gather", 4 * body_scale,
             "CP: KV all-gathered within the cp group per attention")
        _add(budget, explain, "reduce-scatter", 2 * body_scale,
             "CP: S-sharded stream scatter at block exits")
        _add(budget, explain, "all-to-all", 2 * body_scale,
             "CP: head<->sequence resharding around attention")
    if feats["mlp_cp"]:
        _add(budget, explain, "all-gather", 3 * body_scale,
             "MLP-CP: MLP stream gathered back to the replicated residual")
        _add(budget, explain, "reduce-scatter", 1 * body_scale,
             "MLP-CP: scatter into the S-sharded MLP stream")
        _add(budget, explain, "all-to-all", 1 * body_scale,
             "MLP-CP: partitioner resharding at the MLP boundary")

    if feats["flash_decoding"]:
        _add(budget, explain, "all-reduce", 2 * body_scale,
             "flash decoding: distributed softmax over KV-S shards")
        _add(budget, explain, "all-gather", 2 * body_scale,
             "flash decoding: per-shard partial attention assembly")
    if feats["attention_dp"]:
        _add(budget, explain, "all-gather", 3 * body_scale,
             "attention-DP: batch-sharded decode regrouped at block exits")
        _add(budget, explain, "all-to-all", 2 * body_scale,
             "attention-DP: batch<->head resharding around attention")
        _add(budget, explain, "collective-permute", 2 * body_scale,
             "attention-DP: dp-group rotation")
        _add(budget, explain, "all-reduce", 1 * body_scale,
             "attention-DP: cross-group reduction")

    if getattr(arch, "moe", None) is not None:
        _moe_budget(budget, explain, tc, arch.moe, decode_like, body_scale,
                    world)

    if tc.quantized:
        _add(budget, explain, "all-reduce", 1 * body_scale,
             "quantized matmul: scale/accumulator reduction")

    if getattr(tc, "pp_degree", 1) > 1:
        _add(budget, explain, "collective-permute", 4,
             "pipeline parallel: stage-boundary activation shifts")
        _add(budget, explain, "all-gather", 2,
             "pipeline parallel: final-stage output broadcast")

    return budget, explain


def _moe_budget(
    budget: Dict[str, int],
    explain: List[str],
    tc,
    moe,
    decode_like: bool,
    body_scale: int,
    world: int,
) -> None:
    """MoE dispatch/combine collective budget.

    **TPxEP meshes** (an explicit ``moe_ep_degree`` or a
    ``hybrid_sharding_config``) get EXACT derived counts instead of the old
    generous flat budget: a sharded layer always takes the sorted form
    (ops/moe.py ``expert_form``, ``_sparse_moe``), which dispatches tokens by
    a LOCAL gather inside ``shard_map`` (every shard
    holds the replicated token stream) and combines with **one psum over
    the (ep[, epx], tp) world** per layer body — so the budget is one
    all-reduce per body (plus one for the always-on shared expert), and
    **zero** all-to-all / all-gather. The degrees come from the CONFIG
    (``moe_ep_degree`` / ``hybrid_sharding_config.moe_{cte,tkg}_ep_degree``
    with the per-phase regime picked by the submodel kind), never from the
    compiled arch — a regime typo must blow past the budget, not raise it.

    Regimes WITHOUT declared degrees (full-world EP from the family
    builder's ``ep_policy``, expert-internal TP) keep the
    flat allowance: GSPMD owns their lowering and its collective pattern is
    not pinned by this repo's code.
    """
    hsc = getattr(tc, "hybrid_sharding_config", None)
    ep_degree = None
    if hsc is not None:
        ep_degree = (
            hsc.moe_tkg_ep_degree if decode_like else hsc.moe_cte_ep_degree
        )
        regime = (
            f"per-phase hybrid TPxEP ({'tkg' if decode_like else 'cte'} "
            f"regime: moe_{'tkg' if decode_like else 'cte'}_ep_degree="
            f"{ep_degree})"
        )
    elif getattr(tc, "moe_ep_degree", None) and tc.moe_ep_degree > 1:
        ep_degree = tc.moe_ep_degree
        regime = f"hybrid TPxEP (moe_ep_degree={ep_degree})"

    if ep_degree is not None:
        tp_inner = max(world // ep_degree, 1)
        n_ar = 1
        why = (
            f"MoE {regime} x tp={tp_inner}: sorted dispatch is a local "
            "gather; combine is ONE psum over the (ep, tp) world"
        )
        if getattr(moe, "shared_expert_intermediate_size", None):
            n_ar += 1
            why += "; +1 shared-expert row-parallel psum"
        _add(budget, explain, "all-reduce", n_ar * body_scale, why)
        explain.append(
            "+0 all-to-all, +0 all-gather: TPxEP dispatch/combine counts "
            "derived from moe_*_degree (no flat allowance)"
        )
        return

    _add(budget, explain, "all-to-all", 4 * body_scale,
         "MoE: token dispatch/combine over the expert axis")
    _add(budget, explain, "all-gather", 4 * body_scale,
         "MoE: router logits / expert outputs regrouped")
    _add(budget, explain, "all-reduce", 2 * body_scale,
         "MoE: expert-parallel partial-sum reduction")


def over_budget(
    observed: Dict[str, int], budget: Dict[str, int]
) -> Dict[str, Tuple[int, int]]:
    """``{op: (observed, budget)}`` for every op type exceeding its budget."""
    return {
        op: (observed.get(op, 0), budget.get(op, 0))
        for op in COLLECTIVE_OPS
        if observed.get(op, 0) > budget.get(op, 0)
    }
