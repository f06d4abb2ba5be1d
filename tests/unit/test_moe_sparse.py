"""The expert layer's three forms — sorted (ragged_dot), dense, and a share's
live pairs compacted into slots — and what chooses among them
(``expert_form``); FLOP scaling in top_k (not num_experts), routing variants,
and the hybrid TPxEP sharding plan.

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

from nxdi_tpu.analysis.costs import CHIP_SPECS
from nxdi_tpu.ops.moe import (
    MoEArch,
    compact_capacity,
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


#: what a share's arch resolves from its TpuConfig (models/deepseek, models/mimo_v2): a v5e's 240.5 rows
V5E_RIDGE = CHIP_SPECS["v5e"].ridge_rows
#: a share built for a chip whose held experts outnumber twice its top k (Solar-Open2's 40 of 320, top 8, in small)
WIDE_SHARE = dict(num_experts=80, held_experts=10, ridge_rows=V5E_RIDGE)


@pytest.mark.parametrize(
    "fields, rows, chosen",
    [(dict(), 10, "sorted"), (dict(held_experts=4), 10, "dense"), (dict(held_experts=4), 1, "sorted"),
     (WIDE_SHARE, 64, "dense"), (WIDE_SHARE, 512, "compact")],
    ids=["whole-layer", "share-of-4", "share-at-one-row", "wide-share-dense", "wide-share-compact"],
)
def test_the_layers_own_choice_equals_both_pinned_forms(fields, rows, chosen):
    """``dispatch=None``: the layer chooses from its shapes, records what it
    chose, and gives what either pinned form gives."""
    from nxdi_tpu.ops import moe as moe_ops

    rng = np.random.default_rng(4)
    H = 16
    held = fields.get("held_experts")
    own = MoEArch(**{**BASE, **fields}, first_held=0 if held is None else 2)
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


# -- the compact form: a share's live pairs in slots ---------------------------------------


def _share(experts, top_k, held, first=2, H=16, seed=0, **fields):
    """A share ``[first, first + held)`` of ``experts``, built for a v5e, and its parameters."""
    moe = MoEArch(num_experts=experts, top_k=top_k, intermediate_size=32, held_experts=held,
                  first_held=first, ridge_rows=V5E_RIDGE, **fields)
    p = _params(np.random.default_rng(seed), moe, H)
    p["experts"] = jax.tree_util.tree_map(lambda w: w[first: first + held], p["experts"])
    if moe.router_bias:
        p["router"]["b"] = jnp.zeros((experts,), jnp.float32)
    if moe.shared_expert_intermediate_size:
        r = np.random.default_rng(seed + 1)
        p["shared_expert"] = {
            k: {"w": jnp.asarray(r.standard_normal(shape) * 0.1, jnp.float32)}
            for k, shape in (("gate_proj", (H, 24)), ("up_proj", (H, 24)), ("down_proj", (24, H)))
        }
    return moe, p


def _forms(moe, p, x, **kw):
    """``{form: (output, held pairs, slot rows or None)}`` of the three pinned forms."""
    out = {}
    for form in ("dense", "sorted", "compact"):
        tally = []
        y = moe_block(None, dataclasses.replace(moe, dispatch=form), p, x, held_tally=tally, **kw)
        out[form] = (np.asarray(y), *tally[0])
    return out


_SHARES = {"4-of-16": (16, 2, 4), "16-of-256": (256, 8, 16), "10-of-80": (80, 2, 10)}
_ROUTERS = {
    "renormalised": dict(norm_topk_prob=True),
    "biased-sigmoid": dict(sigmoid_routing=True, correction_bias=True, routed_scaling=2.5, norm_topk_prob=True),
    "shared-expert": dict(norm_topk_prob=False, shared_expert_intermediate_size=24),
}


@pytest.mark.parametrize("rows", [64, 512])
@pytest.mark.parametrize("router", sorted(_ROUTERS))
@pytest.mark.parametrize("share", sorted(_SHARES))
def test_compact_equals_both_pinned_forms(share, router, rows):
    """Float32, a share with no exchange: the slots give what every held expert
    on every row gives and what the sorted pairs give, and count the same pairs.
    Each share built for a chip chooses dense at 64 rows and compact at 512,
    also where its held experts outnumber twice its top k (10 of 80, top 2)."""
    moe, p = _share(*_SHARES[share], seed=rows, **_ROUTERS[router])
    assert expert_form(moe, rows) == ("dense" if rows < 512 else "compact")
    x = jnp.asarray(np.random.default_rng(rows + 1).standard_normal((1, rows, 16)), jnp.float32)
    got = _forms(moe, p, x)
    for form in ("dense", "sorted"):
        np.testing.assert_allclose(got["compact"][0], got[form][0], atol=1e-5)
        assert int(got[form][1]) == int(got["compact"][1]) and got[form][2] is None
    slots = moe.experts_here * compact_capacity(moe, rows)
    assert int(got["compact"][2]) % slots == 0 and int(got["compact"][2]) >= slots


@pytest.mark.parametrize(
    "variant",
    [dict(llama4_router=True), dict(topk_softmax=True, expert_bias=True, gptoss_glu=True, glu_limit=7.0)],
    ids=["llama4-input-scale", "gptoss-biases"],
)
def test_compact_computes_the_variants_the_families_reach(variant):
    moe, p = _share(16, 2, 4, seed=3, **variant)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 96, 16)), jnp.float32)
    got = _forms(moe, p, x)
    np.testing.assert_allclose(got["compact"][0], got["dense"][0], atol=1e-5)
    np.testing.assert_allclose(got["compact"][0], got["sorted"][0], atol=1e-5)


@pytest.mark.parametrize(
    "push, shared, trips_of",
    [(+50.0, False, lambda rows, ce: -(-rows // ce)), (+50.0, True, lambda rows, ce: -(-rows // ce)),
     (-50.0, False, lambda rows, ce: 0), (-50.0, True, lambda rows, ce: 0)],
    ids=["one-expert-takes-every-row", "every-row-and-shared", "no-pair-held", "no-pair-held-shared-only"],
)
def test_compact_drops_no_pair_at_any_load(push, shared, trips_of):
    """The live count is data. A router that sends EVERY row to one held
    expert fills its slots several times over (load = rows, ``ceil(rows / Ce)``
    trips); one that sends no row to a held expert runs no trip and the
    layer gives its shared expert's output, or zero. Both equal the dense
    form, and the slot rows are ``held experts x Ce x trips``."""
    rows = 700
    fields = dict(shared_expert_intermediate_size=24) if shared else {}
    moe, p = _share(256, 8, 16, first=32, seed=9, router_bias=True, **fields)
    held = slice(32, 33) if push > 0 else slice(32, 48)
    p["router"]["b"] = p["router"]["b"].at[held].set(push)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, rows, 16)), jnp.float32)
    got = _forms(moe, p, x)
    ce = compact_capacity(moe, rows)
    assert ce == 256 and int(got["compact"][2]) == 16 * ce * trips_of(rows, ce)  # 3 trips, or none
    assert int(got["compact"][1]) == int(got["dense"][1]) and (int(got["dense"][1]) >= rows) == (push > 0)
    np.testing.assert_allclose(got["compact"][0], got["dense"][0], atol=1e-5)
    if push < 0:
        want = 0.0 if not shared else np.asarray(moe_block(None, dataclasses.replace(
            moe, dispatch="dense"), {**p, "experts": jax.tree_util.tree_map(jnp.zeros_like, p["experts"])}, x))
        np.testing.assert_allclose(got["compact"][0], want, atol=1e-6)


def test_compact_gives_bucket_padding_no_slot():
    """Rows behind a batch row's last real token are one pad token over and
    over and route alike: the compact form gives them no slot and counts no
    pair of theirs; the real rows read as in the dense form, which (like the
    sorted one) computes every row."""
    moe, p = _share(16, 2, 4, seed=11, router_bias=True)
    p["router"]["b"] = p["router"]["b"].at[3].set(50.0)  # every row picks held expert 3
    rng = np.random.default_rng(12)
    real, S = 40, 128
    x = np.repeat(rng.standard_normal((2, 1, 16)), S, axis=1)  # padding: one row repeated
    x[:, :real] = rng.standard_normal((2, real, 16))
    x = jnp.asarray(x, jnp.float32)
    last = jnp.asarray([real - 1, real - 11], jnp.int32)
    got = _forms(moe, p, x, last_real=last)
    live = np.arange(S)[None, :] <= np.asarray(last)[:, None]
    np.testing.assert_allclose(got["compact"][0][live], got["dense"][0][live], atol=1e-5)
    assert np.all(got["compact"][0][~live] == 0.0) and np.any(got["dense"][0][~live] != 0.0)
    ce = compact_capacity(moe, 2 * S)
    assert int(got["compact"][2]) == 4 * ce * -(-(2 * real - 10) // ce)  # the hot expert's load: the live rows
    assert int(got["dense"][1]) >= 2 * S > int(got["compact"][1]) >= 2 * real - 10


# (rows, held experts or None = the whole layer, top k, router width E)
_CELL = [(rows, 16, 8, 256) for rows in (128, 256, 512, 1024, 2048, 4096)]
_CELL_WANT = {128: "dense", 256: "dense", 512: "compact", 1024: "compact", 2048: "compact", 4096: "compact"}
#: solar-open2-ep8's share: 40 of 320 held, top 8 (rule (a) up to 39 rows, dense to 511, compact from 512)
_SOLAR = [(rows, 40, 8, 320) for rows in (1, 39, 40, 128, 256, 511, 512, 1024)]
_SOLAR_WANT = {1: "sorted", 39: "sorted", 40: "dense", 128: "dense", 256: "dense", 511: "dense",
               512: "compact", 1024: "compact"}
_WHOLE = [(rows, None, k, e) for e, k in ((8, 2), (60, 4), (128, 8), (256, 8))
          for rows in (1, 16, 128, 1024, 8192)]


@pytest.mark.parametrize(
    "rows, held, top_k, experts, want",
    [(*c, _CELL_WANT[c[0]]) for c in _CELL]
    + [(1, 16, 8, 256, "sorted"), (16, 16, 8, 256, "sorted")]
    + [(*c, "sorted") for c in _WHOLE]
    + [(*c, _SOLAR_WANT[c[0]]) for c in _SOLAR]
    # a share built for a chip needs no held count under twice its top k (rule (b) is for layers built
    # for no chip): 17 of 256 held take dense and compact as 16 do
    + [(128, 17, 8, 256, "dense"), (32, 16, 8, 256, "dense"), (31, 16, 8, 256, "sorted")]
    # rule (c): a share whose slots (no fewer than the ridge's 256 on a v5e) are at most half its
    # row-experts: 16 of 16 held has nothing to compact, 511 rows are short of twice the ridge
    + [(1024, 17, 8, 256, "compact"), (1024, 16, 8, 16, "dense"), (1024, 4, 2, 16, "compact"),
       (1024, 4, 2, 8, "compact"), (1024, 4, 2, 7, "dense"), (511, 16, 8, 256, "dense"),
       (8192, 16, 8, 256, "compact")],
    ids=lambda v: str(v),
)
def test_expert_form_table(rows, held, top_k, experts, want):
    """The rule in rows, held experts, top k and router width alone: of the
    routed cells' programs (a share of 16 of 256, top 8, and Solar-Open2's 40
    of 320, top 8) token generation at 128 rows and CTE[256] are dense and the
    prefill buckets from 512 rows up compact; a share below one row an expert
    (1 and 16 rows of 16 of 256, 39 rows of 40 of 320), and a whole published
    layer at every row count, are sorted."""
    moe = MoEArch(num_experts=experts, top_k=top_k, intermediate_size=8, held_experts=held,
                  ridge_rows=V5E_RIDGE)
    assert expert_form(moe, rows) == want
    for form in ("dense", "sorted", "compact"):  # a pin is a pin
        assert expert_form(dataclasses.replace(moe, dispatch=form), rows) == form
    # built for no chip (a bare MoEArch), nothing says where compute-bound begins: never compact, and
    # dense only where the held experts are at most twice the top k (rule (b)): Solar's 40 of 320 stays
    # sorted at every row count
    bare = expert_form(dataclasses.replace(moe, ridge_rows=None), rows)
    here = experts if held is None else held
    assert bare == ("sorted" if want == "sorted" or here > 2 * top_k else "dense")


@pytest.mark.parametrize("chip, crossing", [("v4", 448), ("v5e", 512), ("v5p", 352), ("v6e", 1120)])
def test_the_crossing_follows_the_declared_chips_ridge(chip, crossing):
    """Rule (c) reads the ridge the arch was built with, not the attached
    device: twice its slots (the ridge in tiles of 16) is where compact begins."""
    moe = MoEArch(num_experts=256, top_k=8, intermediate_size=8, held_experts=16,
                  ridge_rows=CHIP_SPECS[chip].ridge_rows)
    assert 2 * compact_capacity(moe, crossing) == crossing
    assert expert_form(moe, crossing) == "compact" and expert_form(moe, crossing - 1) == "dense"


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
    share = MoEArch(num_experts=256, top_k=8, intermediate_size=8, held_experts=16, ridge_rows=V5E_RIDGE)
    with jax.set_mesh(build_mesh(tp_degree=1)):
        assert expert_form(share, 128) == "dense"
        assert expert_form(share, 1) == "sorted"
        assert expert_form(share, 1024) == "compact"


@pytest.mark.parametrize("rows", [128, 1024, 4096])
@pytest.mark.parametrize("fields", [dict(ep=True), dict(hybrid_ep=True), dict()],
                         ids=["expert-axis", "hybrid-axes", "intermediate-axis"])
def test_no_row_count_makes_a_sharded_layer_compact(fields, rows):
    """The compact form is a share's, computed with no exchange: under an
    expert or intermediate mesh axis every row count stays sorted."""
    from nxdi_tpu.parallel.mesh import build_mesh

    few = MoEArch(num_experts=64, top_k=8, intermediate_size=8, **fields)
    mesh = build_mesh(tp_degree=8, ep_degree=2) if fields.get("hybrid_ep") else build_mesh(tp_degree=8)
    with jax.set_mesh(mesh):
        assert expert_form(few, rows) == "sorted"


@pytest.mark.parametrize("rows, top_k, experts, want", [
    (512, 8, 256, 256), (1024, 8, 256, 256), (4096, 8, 256, 256), (8192, 8, 256, 512), (64, 2, 16, 256),
    (4096, 2, 16, 1024), (4100, 2, 16, 1040),
], ids=str)
def test_compact_capacity_is_twice_the_expected_load_and_no_less_than_the_ridge(rows, top_k, experts, want):
    """Static, from the shapes and the ridge of the chip the arch was built for
    (a v5e's 240.5 rows), in whole tiles of 16 rows; with no chip, the load alone."""
    assert 240 < V5E_RIDGE < 241
    moe = MoEArch(num_experts=experts, top_k=top_k, intermediate_size=8, held_experts=4,
                  ridge_rows=V5E_RIDGE)
    assert compact_capacity(moe, rows) == want
    bare = dataclasses.replace(moe, ridge_rows=None)
    assert compact_capacity(bare, rows) == -(-2 * rows * top_k // (16 * experts)) * 16


def test_a_pin_is_one_of_the_three_forms():
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
