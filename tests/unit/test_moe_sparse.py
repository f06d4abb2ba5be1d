"""Sparse (ragged_dot) MoE dispatch — equivalence with dense dispatch, FLOP
scaling in top_k (not num_experts), routing variants, and the hybrid TPxEP
sharding plan.

Reference behaviors being matched: blockwise expert dispatch in
modules/moe_v2.py:23-132 (ExpertMLPsV2), TPxEP process groups (:135-161), and
HF router semantics per family (mixtral softmax-top-k, gpt-oss
top-k-then-softmax, deepseek-V3 sigmoid grouped top-k).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nxdi_tpu.ops.moe import (
    MoEArch,
    expert_parallel_specs,
    moe_block,
    moe_parallel_fields,
    route_topk,
)


def _params(rng, moe: MoEArch, H: int, expert_bias=False):
    E, I = moe.num_experts, moe.intermediate_size

    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)

    p = {
        "router": {"w": r(H, E)},
        "experts": {
            "gate_proj": {"w": r(E, H, I)},
            "up_proj": {"w": r(E, H, I)},
            "down_proj": {"w": r(E, I, H)},
        },
    }
    if moe.expert_bias:
        p["experts"]["gate_proj"]["b"] = r(E, I)
        p["experts"]["up_proj"]["b"] = r(E, I)
        p["experts"]["down_proj"]["b"] = r(E, H)
    if moe.correction_bias:
        p["router"]["e_bias"] = r(E)
    return p


BASE = dict(num_experts=8, top_k=2, intermediate_size=32)


@pytest.mark.parametrize(
    "variant",
    [
        dict(),
        dict(norm_topk_prob=False),
        dict(topk_softmax=True, expert_bias=True, gptoss_glu=True, glu_limit=7.0),
        dict(llama4_router=True),
        dict(sigmoid_routing=True, n_group=4, topk_group=2, routed_scaling=2.5,
             correction_bias=True, norm_topk_prob=True),
        dict(sigmoid_routing=False, n_group=4, topk_group=2, routed_scaling=16.0,
             norm_topk_prob=False),
    ],
    ids=["softmax", "no-renorm", "gptoss", "llama4", "deepseek-v3", "deepseek-v2"],
)
def test_sparse_matches_dense(variant):
    rng = np.random.default_rng(0)
    H = 16
    sparse = MoEArch(**BASE, dispatch="sparse", **variant)
    dense = MoEArch(**BASE, dispatch="dense", **variant)
    p = _params(rng, sparse, H)
    x = jnp.asarray(rng.standard_normal((2, 5, H)), jnp.float32)
    out_s = moe_block(None, sparse, p, x)
    out_d = moe_block(None, dense, p, x)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), atol=1e-5)


def _expert_matmul_flops(moe: MoEArch, H=32, T=8):
    """Ideal expert-compute FLOPs from the traced graph.

    Sparse: ragged_dot processes each of its T*top_k rows against exactly ONE
    (in, out) expert slice — 2*rows*in*out FLOPs on the TPU grouped-matmul
    lowering, independent of E (the CPU *lowering* decomposes per-group, so
    runtime cost_analysis on the test backend can't see this; the op-level
    count is the contract). Dense: einsum contracts over all E experts."""
    rng = np.random.default_rng(0)
    p = _params(rng, moe, H)
    x = jnp.asarray(rng.standard_normal((1, T, H)), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x: moe_block(None, moe, p, x))(p, x)

    flops = 0
    seen_ragged = 0

    def walk(jp):
        nonlocal flops, seen_ragged
        for eqn in jp.eqns:
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):  # ClosedJaxpr
                    walk(v.jaxpr)
                elif hasattr(v, "eqns"):
                    walk(v)
            if eqn.primitive.name == "ragged_dot_general":
                lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
                rows, contract = lhs[-2], lhs[-1]
                out = rhs[-1]
                flops += 2 * rows * contract * out
                seen_ragged += 1
            elif eqn.primitive.name == "dot_general":
                lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
                if len(lhs) >= 2 and len(rhs) == 3:  # batched expert einsum
                    flops += 2 * int(np.prod(lhs[-2:])) * rhs[-1] * (
                        rhs[0] if len(lhs) == 2 else 1
                    )
        return

    walk(jaxpr.jaxpr)
    return flops, seen_ragged


def test_sparse_flops_scale_with_topk_not_experts():
    """Decode-shaped MoE: dense dispatch pays E/top_k x the expert FLOPs; the
    sparse path's grouped-matmul work is fixed at T*top_k rows as E grows."""
    small = dataclasses.replace(MoEArch(**BASE), num_experts=8)
    big = dataclasses.replace(MoEArch(**BASE), num_experts=64)
    f_small, r_small = _expert_matmul_flops(small)
    f_big, r_big = _expert_matmul_flops(big)
    assert r_small == 3 and r_big == 3  # gate/up/down all grouped
    assert f_big == f_small, (f_small, f_big)  # E-independent

    d_small, _ = _expert_matmul_flops(dataclasses.replace(small, dispatch="dense"))
    d_big, _ = _expert_matmul_flops(dataclasses.replace(big, dispatch="dense"))
    assert d_big >= 7.9 * d_small, (d_small, d_big)  # sanity: dense scales in E

    # and the sparse path scales linearly in top_k
    k4, _ = _expert_matmul_flops(dataclasses.replace(small, top_k=4))
    assert k4 == 2 * f_small, (f_small, k4)


def test_deepseek_v3_routing_golden():
    """route_topk sigmoid grouped-top-k vs a straight numpy transcription of
    HF DeepseekV3TopkRouter (selection uses bias-corrected scores, weights use
    raw sigmoid scores, renormalized then scaled)."""
    rng = np.random.default_rng(3)
    T, E, G, KG, K = 5, 16, 4, 2, 4
    logits = rng.standard_normal((T, E)).astype(np.float32)
    e_bias = rng.standard_normal(E).astype(np.float32)
    moe = MoEArch(
        num_experts=E, top_k=K, intermediate_size=8, sigmoid_routing=True,
        n_group=G, topk_group=KG, routed_scaling=2.5, correction_bias=True,
        norm_topk_prob=True,
    )
    vals, idx = route_topk(jnp.asarray(logits), moe, {"e_bias": jnp.asarray(e_bias)})
    vals, idx = np.asarray(vals), np.asarray(idx)

    scores = 1.0 / (1.0 + np.exp(-logits))
    select = scores + e_bias
    group_scores = np.sort(select.reshape(T, G, E // G), axis=-1)[:, :, -2:].sum(-1)
    for t in range(T):
        keep_groups = np.argsort(-group_scores[t])[:KG]
        masked = np.where(
            np.isin(np.arange(E) // (E // G), keep_groups), select[t], -np.inf
        )
        top = np.argsort(-masked)[:K]
        assert set(idx[t]) == set(top), (t, idx[t], top)
        w = scores[t][idx[t]]
        w = w / (w.sum() + 1e-20) * 2.5
        np.testing.assert_allclose(vals[t], w, atol=1e-6)


def test_hybrid_tpxep_specs():
    """moe_ep_degree carves the ep axis: experts shard over ep, expert
    intermediates over tp, and both at once on each weight (2-D sharding)."""

    class TC:
        tp_degree = 8
        moe_ep_degree = 2
        moe_dispatch = "sparse"

    fields = moe_parallel_fields(TC, 8)
    assert fields == {"ep": False, "hybrid_ep": True, "dispatch": "sparse"}
    moe = MoEArch(**BASE, **fields)
    specs = expert_parallel_specs(moe)
    from jax.sharding import PartitionSpec as P

    assert specs["experts"]["gate_proj"]["w"] == P("ep", None, ("epx", "tp"))
    assert specs["experts"]["down_proj"]["w"] == P("ep", ("epx", "tp"), None)

    class TC2:
        tp_degree = 8
        moe_ep_degree = None
        moe_dispatch = "sparse"

    moe2 = MoEArch(**BASE, **moe_parallel_fields(TC2, 8))
    assert moe2.ep and not moe2.hybrid_ep
    specs2 = expert_parallel_specs(moe2)
    assert specs2["experts"]["gate_proj"]["w"] == P(("ep", "epx", "tp"), None, None)

    with pytest.raises(ValueError, match="must divide"):
        moe_parallel_fields(TC, 9)


def test_per_phase_hybrid_specs_and_duplication():
    """hybrid_sharding_config: prefill specs TP-heavy, decode copy EP-heavy
    (reference: HybridShardingConfig config.py:1060 + mlp_op_tkg weight
    duplication in the hybrid preshard hook)."""
    from jax.sharding import PartitionSpec as P

    from nxdi_tpu.config import HybridShardingConfig
    from nxdi_tpu.ops.moe import duplicate_per_phase_experts

    class TC:
        tp_degree = 8
        moe_ep_degree = None
        moe_dispatch = "sparse"
        hybrid_sharding_config = HybridShardingConfig(
            moe_cte_ep_degree=2, moe_tkg_ep_degree=8
        )

    fields = moe_parallel_fields(TC, 8)
    assert fields["per_phase_hybrid"] and fields["hybrid_ep"]
    moe = MoEArch(**BASE, **fields)
    specs = expert_parallel_specs(moe)
    # prefill: experts over ep (2), intermediate over epx x tp (4x1... world/2)
    assert specs["experts"]["gate_proj"]["w"] == P("ep", None, ("epx", "tp"))
    # decode: experts over ep x epx (8), intermediate over tp
    assert specs["experts_tkg"]["gate_proj"]["w"] == P(("ep", "epx"), None, "tp")
    assert specs["experts_tkg"]["down_proj"]["w"] == P(("ep", "epx"), "tp", None)

    rng = np.random.default_rng(0)
    params = {"layers": _params(rng, moe, 16)}
    dup = duplicate_per_phase_experts(params)
    assert set(dup["layers"]) == {"router", "experts", "experts_tkg"}
    np.testing.assert_array_equal(
        dup["layers"]["experts_tkg"]["gate_proj"]["w"],
        dup["layers"]["experts"]["gate_proj"]["w"],
    )


def test_per_phase_hybrid_block_matches_both_phases():
    """The decode-phase block (EP-heavy copy) must produce the same numbers
    as the prefill-phase block on an 8-device mesh."""
    import jax

    from nxdi_tpu.config import HybridShardingConfig
    from nxdi_tpu.ops.moe import duplicate_per_phase_experts
    from nxdi_tpu.parallel.mesh import build_mesh

    class TC:
        tp_degree = 8
        moe_ep_degree = None
        moe_dispatch = "sparse"
        hybrid_sharding_config = HybridShardingConfig(
            moe_cte_ep_degree=2, moe_tkg_ep_degree=8
        )

    fields = moe_parallel_fields(TC, 8)
    moe_cte = MoEArch(**BASE, **fields)
    moe_tkg = dataclasses.replace(moe_cte, phase="decode")
    rng = np.random.default_rng(1)
    p = duplicate_per_phase_experts(_params(rng, moe_cte, 16))
    x = jnp.asarray(rng.standard_normal((2, 4, 16)) * 0.3, jnp.float32)

    ref = moe_block(None, dataclasses.replace(moe_cte, hybrid_ep=False,
                                              per_phase_hybrid=False), p, x)

    mesh = build_mesh(tp_degree=8, ep_degree=2, epx_degree=4)
    with jax.set_mesh(mesh):
        out_cte = jax.jit(lambda p, x: moe_block(None, moe_cte, p, x))(p, x)
        out_tkg = jax.jit(lambda p, x: moe_block(None, moe_tkg, p, x))(p, x)
    np.testing.assert_allclose(np.asarray(out_cte), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out_tkg), np.asarray(ref), atol=2e-5)
