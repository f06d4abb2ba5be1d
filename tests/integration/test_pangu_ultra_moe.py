"""``pangu_ultra_moe`` (openPangu-Ultra-MoE: MLA + a sigmoid router over
routed experts + a shared expert, four norms a layer) on the PAGED path, as one
chip's share of an expert-parallel deployment: prefill, then decoding through
the paged latent pool in the absorbed form, logits against the plain
reference's full forward pass (``benchmark/references/mla_moe_decoder.py``:
float32, non-absorbed, nothing of the program's)."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nxdi_tpu.config import TpuConfig
from nxdi_tpu.models.deepseek import modeling_deepseek as family
from nxdi_tpu.models.registry import get_family
from nxdi_tpu.runtime.application import TpuModelForCausalLM
from nxdi_tpu.runtime.block_manager import BlockSpaceManager

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BLOCK = 8

#: hidden 64, 4 heads, 2 dense + 2 expert layers, 16 experts of which 4 held, top 2
TOY = dict(
    model_type="pangu_ultra_moe", hidden_size=64, intermediate_size=128, num_hidden_layers=4,
    first_k_dense_replace=2, num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    moe_intermediate_size=32, n_routed_experts=4, n_routed_experts_total=16,
    first_routed_expert=8, n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=25600000, sandwich_norm=True,
    hidden_act="silu", num_nextn_predict_layers=1, attention_bias=False,
    max_position_embeddings=4096, tie_word_embeddings=False,
)


def _reference():
    path = os.path.join(ROOT, "benchmark", "references", "mla_moe_decoder.py")
    spec = importlib.util.spec_from_file_location("mla_moe_decoder_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _PinnedForm:
    """The family, with the expert layer's form pinned on every arch it builds
    (``MoEArch.dispatch``: what a test sets to put the two forms side by side;
    None leaves the layer its own choice)."""

    def __init__(self, form):
        self.form = form

    def __getattr__(self, name):
        return getattr(family, name)

    def build_arch(self, config, **overrides):
        arch = family.build_arch(config, **overrides)
        return dataclasses.replace(arch, moe=dataclasses.replace(arch.moe, dispatch=self.form))


def _app(seed=0, expert_form=None, **tpu):
    kwargs = dict(
        tp_degree=1, dtype="float32", seq_len=64, max_context_length=32, batch_size=2,
        ctx_batch_size=1, tkg_batch_size=2, is_block_kv_layout=True, pa_block_size=BLOCK,
        pa_num_blocks=24, skip_warmup=True, output_logits=True,
    )
    kwargs.update(tpu)
    _, cfg_cls = get_family("pangu_ultra_moe")
    config = cfg_cls(TpuConfig(**kwargs), load_config=lambda: dict(TOY))

    class App(TpuModelForCausalLM):
        def build_params(self):
            struct = self.build_params_struct()
            flat, treedef = jax.tree_util.tree_flatten_with_path(struct)
            rng = np.random.default_rng(seed)
            leaves = [
                jnp.ones(s.shape, s.dtype)
                if any(str(getattr(k, "key", "")).endswith("norm") for k in path)
                else jnp.asarray(rng.standard_normal(s.shape) * 0.08, s.dtype)
                for path, s in flat
            ]
            return jax.tree_util.tree_unflatten(treedef, leaves)

    app = App("<seeded>", config, model_family=_PinnedForm(expert_form))
    app.load()
    return app


P0 = [5, 9, 3, 17, 2, 8, 11, 42, 7, 1, 99, 200, 31]
P1 = [7, 13, 21, 4, 33]


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("dispatch", ["sorted", "dense", None], ids=str)
def test_prefill_then_paged_absorbed_decode_matches_the_reference(dispatch, kernel):
    """Two rows of unequal length in scrambled blocks: every prefill and every
    decode step's logits against the reference over the whole sequence, with
    each form of the expert layer pinned and with the layer's own choice (the
    32-row prefill of 4 held experts dense, the 2-row decode sorted)."""
    app = _app(expert_form=dispatch, attn_block_tkg_kernel_enabled=kernel)
    ref = _reference()
    mgr = BlockSpaceManager(24, BLOCK)
    mgr.ensure_capacity(99, 3 * BLOCK)  # burn blocks: tables are not contiguous
    width = app.tpu_config.seq_len // BLOCK
    seqs = {0: list(P0), 1: list(P1)}
    rng = np.random.default_rng(1)

    def check(sid, got):
        want = np.asarray(ref.forward(app.params, TOY, np.asarray(seqs[sid])))[-1]
        np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=2e-3, atol=2e-4)

    for sid, prompt in seqs.items():
        mgr.ensure_capacity(sid, len(prompt) + 9)
        out = app.forward(
            np.asarray([prompt], np.int32), np.arange(len(prompt), dtype=np.int32)[None],
            last_token_index=np.array([len(prompt) - 1], np.int32),
            block_table=mgr.block_table(sid, width)[None, :],
        )
        assert "moe_held_pairs" not in out, "counted in token generation only"
        check(sid, np.asarray(out["logits"])[0, -1])
    mgr.free_seq(99)
    for _ in range(6):
        nxt = rng.integers(0, 256, size=2)
        pos = np.array([[len(seqs[0])], [len(seqs[1])]], np.int32)
        for sid in (0, 1):
            seqs[sid].append(int(nxt[sid]))
        out = app.forward(
            nxt[:, None].astype(np.int32), pos,
            block_table=np.stack([mgr.block_table(0, width), mgr.block_table(1, width)]),
        )
        for sid in (0, 1):
            check(sid, np.asarray(out["logits"])[sid, -1])
        pairs, layers = int(out["moe_held_pairs"]), int(out["moe_routed_layers"])
        assert layers == 2 and 0 <= pairs <= 2 * 2 * 2, (pairs, layers)
    want = "tkg_mla_paged_kernel" if kernel else "tkg_mla_paged_xla"
    tkg = app.models["token_generation_model"]
    assert all(want in p.attention_strategies for p in tkg._programs.values())
    forms = {tag: {f for p in m._programs.values() for f in p.expert_forms}
             for tag, m in app.models.items()}
    assert forms == {"context_encoding_model": {dispatch or "dense"},
                     "token_generation_model": {dispatch or "sorted"}}
    counted = app.telemetry.expert_form_programs
    assert {tuple(counted.labels_of(key).values()) for key in counted.series()} == {
        (tag, f) for tag, fs in forms.items() for f in fs}


def test_the_held_pair_count_is_the_reference_routers():
    """``moe_held_pairs`` of a decode step == the (row, expert) pairs the
    reference's own router puts on the held experts at those positions."""
    app = _app(seed=3)
    ref = _reference()
    mgr = BlockSpaceManager(24, BLOCK)
    width = app.tpu_config.seq_len // BLOCK
    mgr.ensure_capacity(0, len(P0) + 4)
    app.forward(
        np.asarray([P0], np.int32), np.arange(len(P0), dtype=np.int32)[None],
        last_token_index=np.array([len(P0) - 1], np.int32),
        block_table=mgr.block_table(0, width)[None, :],
    )
    out = app.forward(np.array([[77]], np.int32), np.array([[len(P0)]], np.int32),
                      block_table=mgr.block_table(0, width)[None, :])
    # batch padding repeats row 0: the program counts both rows of its batch
    pairs = int(out["moe_held_pairs"])
    assert pairs % 2 == 0 and 0 <= pairs // 2 <= 4
    margins = np.asarray(ref.routing_margins(app.params, TOY, np.asarray(P0 + [77])))
    assert margins.shape == (len(P0) + 1,) and (margins > 0).all()


def test_the_family_states_what_the_config_json_leaves_out():
    _, cfg_cls = get_family("pangu_ultra_moe")
    assert cfg_cls is family.PanguUltraMoeInferenceConfig
    config = cfg_cls(TpuConfig(tp_degree=1, seq_len=64, batch_size=1), load_config=lambda: dict(TOY))
    arch = family.build_arch(config)
    assert arch.sandwich_norm and arch.mla is not None
    moe = arch.moe
    assert (moe.num_experts, moe.held_experts, moe.first_held, moe.top_k) == (16, 4, 8, 2)
    assert moe.sigmoid_routing and not moe.correction_bias and not moe.n_group
    assert moe.norm_topk_prob and moe.routed_scaling == 2.5
    assert moe.shared_expert_intermediate_size == 32
    head, tail = family._segment_archs(config, arch)
    assert (head.num_layers, head.moe, tail.num_layers) == (2, None, 2)
    struct = family.param_shape_struct(config)
    assert struct["layers"][1]["moe"]["experts"]["up_proj"]["w"].shape == (2, 4, 64, 32)
    assert struct["layers"][1]["moe"]["router"]["w"].shape == (2, 64, 16)
    assert "e_bias" not in struct["layers"][1]["moe"]["router"]
    for seg in struct["layers"]:
        assert seg["pre_feedforward_layernorm"].shape == seg["post_feedforward_layernorm"].shape


def test_conversion_reads_the_published_names_and_the_held_experts():
    """A checkpoint in the published layout: ``pre_mlp_layernorm`` /
    ``post_mlp_layernorm``, no ``e_score_correction_bias``, rope columns as
    they are (rotate-half), and only experts 8..11 of 16 are read."""
    _, cfg_cls = get_family("pangu_ultra_moe")
    config = cfg_cls(TpuConfig(tp_degree=1, seq_len=64, batch_size=1, dtype="float32"),
                     load_config=lambda: dict(TOY))
    rng = np.random.default_rng(0)
    w = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": w(256, 64), "model.norm.weight": w(64), "lm_head.weight": w(256, 64)}
    for i in range(4):
        pre = f"model.layers.{i}."
        sd.update({
            pre + "input_layernorm.weight": w(64), pre + "post_attention_layernorm.weight": w(64),
            pre + "pre_mlp_layernorm.weight": w(64), pre + "post_mlp_layernorm.weight": w(64),
            pre + "self_attn.q_a_proj.weight": w(32, 64), pre + "self_attn.q_a_layernorm.weight": w(32),
            pre + "self_attn.q_b_proj.weight": w(4 * 24, 32),
            pre + "self_attn.kv_a_proj_with_mqa.weight": w(40, 64),
            pre + "self_attn.kv_a_layernorm.weight": w(32),
            pre + "self_attn.kv_b_proj.weight": w(4 * 32, 32), pre + "self_attn.o_proj.weight": w(64, 64),
        })
        if i < 2:
            sd.update({pre + f"mlp.{p}_proj.weight": w(*s) for p, s in
                       (("gate", (128, 64)), ("up", (128, 64)), ("down", (64, 128)))})
            continue
        sd[pre + "mlp.gate.weight"] = w(16, 64)
        for j in range(8, 12):  # the held experts are all this checkpoint shard has
            sd.update({pre + f"mlp.experts.{j}.{p}_proj.weight": w(*s) for p, s in
                       (("gate", (32, 64)), ("up", (32, 64)), ("down", (64, 32)))})
        sd.update({pre + f"mlp.shared_experts.{p}_proj.weight": w(*s) for p, s in
                   (("gate", (32, 64)), ("up", (32, 64)), ("down", (64, 32)))})
    params = family.convert_hf_state_dict(sd, config)
    head, tail = params["layers"]
    np.testing.assert_array_equal(tail["pre_feedforward_layernorm"][1], sd["model.layers.3.pre_mlp_layernorm.weight"])
    np.testing.assert_array_equal(head["post_feedforward_layernorm"][0], sd["model.layers.0.post_mlp_layernorm.weight"])
    np.testing.assert_array_equal(tail["moe"]["experts"]["up_proj"]["w"][0, 2],
                                  sd["model.layers.2.mlp.experts.10.up_proj.weight"].T)
    np.testing.assert_array_equal(tail["attn"]["kv_a"]["w"][0], sd["model.layers.2.self_attn.kv_a_proj_with_mqa.weight"].T)
    assert tail["moe"]["router"]["w"].shape == (2, 64, 16) and "e_bias" not in tail["moe"]["router"]
    struct = family.param_shape_struct(config)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(struct)
    for got, want in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(struct)):
        assert got.shape == want.shape
