"""An expert layer told which experts it holds (``MoEArch.held_experts``): one
chip's share of an expert-parallel layer, run without its exchange. The share
test of the model-configs guide, section 4: the parts of the result that all
the shares give, with what every chip computes alike (the shared expert)
counted once, add up to what the uncut layer gives."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nxdi_tpu.ops.moe import MoEArch, expert_parallel_specs, moe_block, moe_shape_struct

H, E, I, K = 24, 16, 20, 2
WHOLE = MoEArch(
    num_experts=E, top_k=K, intermediate_size=I, sigmoid_routing=True, routed_scaling=2.5,
    norm_topk_prob=True, shared_expert_intermediate_size=I,
)


def _params(seed):
    rng = np.random.default_rng(seed)

    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.3, jnp.float32)

    mlp = lambda *lead: {"gate_proj": {"w": r(*lead, H, I)}, "up_proj": {"w": r(*lead, H, I)},  # noqa: E731
                         "down_proj": {"w": r(*lead, I, H)}}
    return {"router": {"w": r(H, E)}, "experts": mlp(E), "shared_expert": mlp()}


def _share(params, lo, n):
    out = dict(params)
    out["experts"] = jax.tree_util.tree_map(lambda w: w[lo: lo + n], params["experts"])
    return out


def _uncut_reference(p, x):
    """The whole layer in plain float32: router over all experts, every chosen
    expert's SwiGLU weighted, the shared expert added once."""
    xt = x.reshape(-1, H)
    scores = jax.nn.sigmoid(xt @ p["router"]["w"])
    top, chosen = jax.lax.top_k(scores, K)
    weight = top / top.sum(-1, keepdims=True) * 2.5

    def swiglu(h, m):
        return (jax.nn.silu(h @ m["gate_proj"]["w"]) * (h @ m["up_proj"]["w"])) @ m["down_proj"]["w"]

    every = jnp.stack([swiglu(xt, jax.tree_util.tree_map(lambda w: w[e], p["experts"]))
                       for e in range(E)], axis=1)  # (T, E, H)
    routed = (jnp.take_along_axis(every, chosen[:, :, None], 1) * weight[:, :, None]).sum(1)
    return routed.reshape(x.shape), swiglu(xt, p["shared_expert"]).reshape(x.shape)


#: each form pinned, and the layer's own choice (None)
FORMS = ["sorted", "dense", None]


@pytest.mark.parametrize("dispatch", FORMS, ids=str)
@pytest.mark.parametrize("held", [4, 1], ids=["4-shares-of-4", "16-shares-of-1"])
def test_the_shares_add_up_to_the_uncut_layer(held, dispatch):
    p = _params(5)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 7, H)), jnp.float32)
    routed, shared = _uncut_reference(p, x)
    total, tallies = 0.0, []
    for lo in range(0, E, held):
        moe = dataclasses.replace(WHOLE, held_experts=held, first_held=lo, dispatch=dispatch)
        tally = []
        part = moe_block(None, moe, _share(p, lo, held), x, held_tally=tally)
        total = total + (part - shared)  # every share computes the shared expert alike
        tallies.append(int(tally[0]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(routed), rtol=2e-5, atol=2e-6)
    assert sum(tallies) == x.shape[0] * x.shape[1] * K, "every (row, expert) pair falls on one share"
    whole = moe_block(None, dataclasses.replace(WHOLE, dispatch=dispatch), p, x)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dispatch", FORMS, ids=str)
def test_holding_every_expert_is_bit_identical_to_the_whole_layer(dispatch):
    p = _params(7)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((3, 5, H)), jnp.bfloat16)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), p)
    whole = moe_block(None, dataclasses.replace(WHOLE, dispatch=dispatch), p, x)
    held_all = moe_block(
        None, dataclasses.replace(WHOLE, dispatch=dispatch, held_experts=E, first_held=0), p, x)
    assert np.array_equal(np.asarray(whole, np.float32), np.asarray(held_all, np.float32))


def test_a_share_holds_its_experts_and_the_whole_router():
    moe = dataclasses.replace(WHOLE, held_experts=4, first_held=8)
    struct = moe_shape_struct(moe, H, 3, jnp.bfloat16)
    assert struct["router"]["w"].shape == (3, H, E)
    assert struct["experts"]["gate_proj"]["w"].shape == (3, 4, H, I)
    assert struct["experts"]["down_proj"]["w"].shape == (3, 4, I, H)
    assert struct["shared_expert"]["up_proj"]["w"].shape == (3, H, I)
    assert set(expert_parallel_specs(moe)) == {"router", "experts", "shared_expert"}
    assert WHOLE.experts_here == E and moe.experts_here == 4


@pytest.mark.parametrize(
    "fields",
    [dict(held_experts=4, first_held=14), dict(held_experts=0), dict(held_experts=4, ep=True),
     dict(held_experts=4, hybrid_ep=True), dict(held_experts=17)],
    ids=["past-the-router", "none", "expert-axis", "hybrid-axis", "more-than-all"],
)
def test_a_share_that_cannot_be_is_refused(fields):
    with pytest.raises(ValueError):
        dataclasses.replace(WHOLE, **fields)


@pytest.mark.parametrize("rows", [1, 7, 24])
def test_the_held_pair_tally_is_the_same_integer_in_either_form(rows):
    """``moe_held_pairs`` counts the router's top k once, ahead of the expert
    computation: a seeded batch reads the same integer under both pinned forms
    and under the layer's own choice, in float32 and after a cast to bf16."""
    share = dataclasses.replace(WHOLE, held_experts=4, first_held=8)
    p = _share(_params(11), 8, 4)
    x = jnp.asarray(np.random.default_rng(12).standard_normal((1, rows, H)), jnp.float32)
    counts = set()
    for dtype in (jnp.float32, jnp.bfloat16):
        # the router reads float32 either way: same logits, same top k
        pd = {**jax.tree_util.tree_map(lambda w: w.astype(dtype), p), "router": p["router"]}
        for form in FORMS:
            tally = []
            moe_block(None, dataclasses.replace(share, dispatch=form), pd, x, held_tally=tally)
            counts.add(int(tally[0]))
    scores = jax.nn.sigmoid(x.reshape(-1, H) @ p["router"]["w"])
    chosen = np.asarray(jax.lax.top_k(scores, K)[1])
    assert counts == {int(((chosen >= 8) & (chosen < 12)).sum())}


# -- the layer-stacked expert weights, whole, with the layer's index (what the
# layer scan hands the sorted form: models/base.py _extract_stacked_weights)
L = 3


def _stacked(seed):
    layers = [_params(seed + i) for i in range(L)]
    names = ("gate_proj", "up_proj", "down_proj")
    return layers, tuple(jnp.stack([p["experts"][k]["w"] for p in layers]) for k in names)


@pytest.mark.parametrize("held, lo", [(None, 0), (4, 8), (1, 15)], ids=["whole", "4-from-8", "the-last"])
@pytest.mark.parametrize("layer", range(L))
def test_the_stacked_weights_and_a_layer_index_give_the_layers_own_result(layer, held, lo):
    layers, stack = _stacked(20)
    moe = dataclasses.replace(WHOLE, held_experts=held, first_held=lo, dispatch="sorted")
    if held is not None:
        stack = tuple(w[:, lo: lo + held] for w in stack)
    x = jnp.asarray(np.random.default_rng(9).standard_normal((2, 6, H)), jnp.float32)
    p = layers[layer] if held is None else _share(layers[layer], lo, held)
    want = moe_block(None, moe, p, x)
    no_w = {**p, "experts": {k: {} for k in p["experts"]}}  # as the scan's xs carry it

    @jax.jit
    def run(li):
        tally = []
        return moe_block(None, moe, no_w, x, held_tally=tally, stacked_experts=(*stack, li)), tally[0]

    got, pairs = run(jnp.int32(layer))
    # the CPU's grouped matmul sums over the other layers' empty groups too
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    tally = []
    moe_block(None, moe, p, x, held_tally=tally)
    assert int(pairs) == int(tally[0])


def test_the_layer_scan_keeps_plain_expert_weights_of_the_sorted_form_out_of_its_xs():
    from types import SimpleNamespace

    from nxdi_tpu.models.base import _extract_stacked_weights

    layers, stack = _stacked(30)
    seg = {"moe": jax.tree_util.tree_map(lambda *w: jnp.stack(w), *layers), "attn": {}}
    arch = SimpleNamespace(moe=WHOLE, mlp_kernel_enabled=False, qkv_kernel_enabled=False)
    rest, mlp_st, qkv_st, moe_st = _extract_stacked_weights(arch, seg, 12)
    assert mlp_st is None and qkv_st is None
    for got, want in zip(moe_st, stack):
        assert got.shape == want.shape == (L, E) + want.shape[2:]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert all(rest["moe"]["experts"][k] == {} for k in ("gate_proj", "up_proj", "down_proj"))
    assert set(rest["moe"]) == set(seg["moe"]) and "w" in seg["moe"]["experts"]["gate_proj"]
    # the dense form reads a layer's slice in place (an einsum operand): left in the
    # xs, be it pinned or what a share of 4 (<= 2 x top 2) chooses once 8 rows x 2 >= 16
    dense = SimpleNamespace(moe=dataclasses.replace(WHOLE, dispatch="dense"),
                            mlp_kernel_enabled=False, qkv_kernel_enabled=False)
    assert _extract_stacked_weights(dense, seg, 12)[3] is None
    share = SimpleNamespace(moe=dataclasses.replace(WHOLE, held_experts=4, first_held=8),
                            mlp_kernel_enabled=False, qkv_kernel_enabled=False)
    held = {**seg, "moe": {**seg["moe"], "experts": jax.tree_util.tree_map(
        lambda w: w[:, 8:12], seg["moe"]["experts"])}}
    assert _extract_stacked_weights(share, held, 8)[3] is None
    assert _extract_stacked_weights(share, held, 7)[3][0].shape[:2] == (L, 4)
    # a quantized leaf is dequantized a layer at a time: left in the xs
    quant = {**seg, "moe": {**seg["moe"], "experts": {
        k: {"qw": v["w"].astype(jnp.int8), "scale": v["w"][..., :1, :]}
        for k, v in seg["moe"]["experts"].items()}}}
    assert _extract_stacked_weights(arch, quant, 12)[3] is None
