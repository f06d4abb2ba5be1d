"""The expert layer's two forms — sorted (ragged_dot) against dense, and what
chooses between them (``expert_form``) — FLOP scaling in top_k (not
num_experts), routing variants, and the hybrid TPxEP sharding plan.

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
    expert_form,
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
    sparse = MoEArch(**BASE, dispatch="sorted", **variant)
    dense = MoEArch(**BASE, dispatch="dense", **variant)
    p = _params(rng, sparse, H)
    x = jnp.asarray(rng.standard_normal((2, 5, H)), jnp.float32)
    out_s = moe_block(None, sparse, p, x)
    out_d = moe_block(None, dense, p, x)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), atol=1e-5)


@pytest.mark.parametrize(
    "held, rows, chosen",
    [(None, 10, "sorted"), (4, 10, "dense"), (4, 1, "sorted")],
    ids=["whole-layer", "share-of-4", "share-at-one-row"],
)
def test_the_layers_own_choice_equals_both_pinned_forms(held, rows, chosen):
    """``dispatch=None``: the layer chooses from its shapes, records what it
    chose, and gives what either pinned form gives."""
    from nxdi_tpu.ops import moe as moe_ops

    rng = np.random.default_rng(4)
    H = 16
    own = MoEArch(**BASE, held_experts=held, first_held=0 if held is None else 2)
    p = _params(rng, own, H)
    if held is not None:
        p["experts"] = jax.tree_util.tree_map(lambda w: w[2: 2 + held], p["experts"])
    x = jnp.asarray(rng.standard_normal((1, rows, H)), jnp.float32)
    assert expert_form(own, rows) == chosen
    moe_ops._FORM_TRACE.clear()
    out = moe_block(None, own, p, x)
    assert moe_ops._FORM_TRACE == [chosen]
    for form in moe_ops.EXPERT_FORMS:
        pinned = moe_block(None, dataclasses.replace(own, dispatch=form), p, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(pinned), atol=1e-5)


# (rows, held experts or None = the whole layer, top k, router width E)
_CELL = [(rows, 16, 8, 256) for rows in (128, 256, 512, 1024)]
_WHOLE = [(rows, None, k, e) for e, k in ((8, 2), (60, 4), (128, 8), (256, 8))
          for rows in (1, 16, 128, 1024, 8192)]


@pytest.mark.parametrize(
    "rows, held, top_k, experts, want",
    [(*c, "dense") for c in _CELL]
    + [(1, 16, 8, 256, "sorted"), (16, 16, 8, 256, "sorted")]
    + [(*c, "sorted") for c in _WHOLE]
    + [(128, 17, 8, 256, "sorted"), (32, 16, 8, 256, "dense"), (31, 16, 8, 256, "sorted")],
    ids=lambda v: str(v),
)
def test_expert_form_table(rows, held, top_k, experts, want):
    """The rule in rows, held experts, top k and router width alone: the routed
    cell's four programs (a share of 16 of 256, top 8) are dense; the same
    share at 1 and 16 rows, and a whole published layer at every row count,
    are sorted."""
    moe = MoEArch(num_experts=experts, top_k=top_k, intermediate_size=8, held_experts=held)
    assert expert_form(moe, rows) == want
    for form in ("dense", "sorted"):  # a pin is a pin
        assert expert_form(dataclasses.replace(moe, dispatch=form), rows) == form


@pytest.mark.parametrize("fields", [dict(ep=True), dict(hybrid_ep=True), dict()],
                         ids=["expert-axis", "hybrid-axes", "intermediate-axis"])
def test_a_layer_under_a_mesh_with_exchange_is_sorted(fields):
    """Under an expert or intermediate mesh axis the sorted form inside
    ``shard_map`` stays, whatever the shapes; a share on a one-chip
    model-parallel world has nothing to exchange and chooses by the rule."""
    from nxdi_tpu.parallel.mesh import build_mesh

    few = MoEArch(num_experts=8, top_k=4, intermediate_size=8, **fields)  # 8 <= 2 x 4
    assert expert_form(few, 128) == "dense"  # no mesh in scope
    mesh = build_mesh(tp_degree=8, ep_degree=2) if fields.get("hybrid_ep") else build_mesh(tp_degree=8)
    with jax.set_mesh(mesh):
        assert expert_form(few, 128) == "sorted"
    share = MoEArch(num_experts=256, top_k=8, intermediate_size=8, held_experts=16)
    with jax.set_mesh(build_mesh(tp_degree=1)):
        assert expert_form(share, 128) == "dense"
        assert expert_form(share, 1) == "sorted"


def test_a_pin_is_one_of_the_two_forms():
    with pytest.raises(ValueError, match="dispatch"):
        MoEArch(**BASE, dispatch="sparse")


def test_moe_dispatch_is_no_option_of_the_tpu_config():
    from nxdi_tpu.config import TpuConfig

    with pytest.raises(ValueError, match="Unknown TpuConfig arguments.*moe_dispatch"):
        TpuConfig(tp_degree=1, moe_dispatch="dense")


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
    # about the sorted form: pinned (8 experts at top 4 would choose dense)
    small = dataclasses.replace(MoEArch(**BASE, dispatch="sorted"), num_experts=8)
    big = dataclasses.replace(MoEArch(**BASE, dispatch="sorted"), num_experts=64)
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

    fields = moe_parallel_fields(TC, 8)
    assert fields == {"ep": False, "hybrid_ep": True}
    moe = MoEArch(**BASE, **fields)
    specs = expert_parallel_specs(moe)
    from jax.sharding import PartitionSpec as P

    assert specs["experts"]["gate_proj"]["w"] == P("ep", None, ("epx", "tp"))
    assert specs["experts"]["down_proj"]["w"] == P("ep", ("epx", "tp"), None)

    class TC2:
        tp_degree = 8
        moe_ep_degree = None

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
