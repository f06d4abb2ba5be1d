"""Parallelism-strategy correctness: every strategy must produce EXACTLY the
same greedy tokens as HF CPU (reference analog: the CP/DP/flash-decode variants
of the llama3.2 integration tests, e.g.
test_llama3_2_1b_4layer_context_parallel.py).

Strategies under test map the reference inventory (SURVEY §2.3) onto GSPMD
policies (parallel/policy.py): SP, CP, attention-DP, flash decoding, and
combinations. All run on the 8-virtual-device CPU mesh from conftest."""

import numpy as np
import pytest

from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
from nxdi_tpu.generation.hf_adapter import HuggingFaceGenerationAdapter
from nxdi_tpu.models.llama import modeling_llama as llama
from nxdi_tpu.runtime.application import TpuModelForCausalLM
from nxdi_tpu.utils.accuracy import hf_greedy_generate as hf_greedy


def _build_app(hf_model, hf_cfg, **tcfg_kwargs):
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    defaults = dict(
        tp_degree=8,
        seq_len=64,
        max_context_length=32,
        batch_size=1,
        dtype="float32",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True,
    )
    defaults.update(tcfg_kwargs)
    cfg = llama.LlamaInferenceConfig(
        TpuConfig(**defaults), load_config=lambda: hf_cfg.to_dict()
    )

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", cfg, model_family=llama)
    app.load()
    return app


PROMPT = np.array([[5, 9, 3, 17, 2, 8, 11, 42]], dtype=np.int64)


@pytest.mark.parametrize(
    "tcfg_kwargs",
    [
        pytest.param(dict(sequence_parallel_enabled=True), id="sp"),
        pytest.param(dict(mlp_cp_degree=8), id="mlp-cp8"),
        pytest.param(dict(cp_degree=2), id="cp2"),
        pytest.param(dict(cp_degree=4), id="cp4"),
        pytest.param(
            dict(cp_degree=2, sequence_parallel_enabled=True), id="cp2+sp-flag"
        ),
        pytest.param(
            dict(attention_dp_degree=2, batch_size=2), id="attn-dp2"
        ),
        pytest.param(dict(cp_degree=2, flash_decoding_enabled=True), id="flash-decode"),
        pytest.param(
            dict(cp_degree=2, attention_dp_degree=2, batch_size=2), id="cp2+dp2"
        ),
        pytest.param(
            dict(tp_degree=4, pp_degree=2, batch_size=2), id="pp2xtp4"
        ),
        pytest.param(
            dict(tp_degree=2, pp_degree=2, batch_size=4, pp_microbatches=4),
            id="pp2-micro4",
        ),
        pytest.param(
            dict(tp_degree=4, pp_degree=2, batch_size=2,
                 sequence_parallel_enabled=True),
            id="pp2+sp",
        ),
    ],
)
def test_parallel_strategy_token_matching(tiny_hf_llama, tcfg_kwargs):
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(hf_model, hf_cfg, **tcfg_kwargs)
    adapter = HuggingFaceGenerationAdapter(app)

    batch = tcfg_kwargs.get("batch_size", 1)
    prompt = np.tile(PROMPT, (batch, 1))
    expected = hf_greedy(hf_model, prompt, max_new_tokens=20)
    actual = adapter.generate(prompt, max_new_tokens=20)
    np.testing.assert_array_equal(actual, expected)


def test_mesh_axes_from_config():
    from nxdi_tpu.parallel.mesh import mesh_from_config

    tc = TpuConfig(tp_degree=8, cp_degree=2, attention_dp_degree=2, batch_size=2)
    mesh = mesh_from_config(tc)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "pp": 1, "dp": 2, "cp": 2, "ep": 1, "epx": 1, "tp": 2
    }
    tc = TpuConfig(tp_degree=4, pp_degree=2, batch_size=2)
    mesh = mesh_from_config(tc)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "pp": 2, "dp": 1, "cp": 1, "ep": 1, "epx": 1, "tp": 4
    }


def test_flash_decoding_requires_single_bucket():
    with pytest.raises(ValueError, match="single token-generation bucket"):
        TpuConfig(
            tp_degree=8, cp_degree=2, flash_decoding_enabled=True, enable_bucketing=True
        )
    with pytest.raises(ValueError, match="cp_degree"):
        TpuConfig(tp_degree=8, flash_decoding_enabled=True)


def test_cache_partition_spec_variants():
    from jax.sharding import PartitionSpec as P

    from nxdi_tpu.kvcache.kv_cache import kv_cache_partition_spec

    tc = TpuConfig(tp_degree=8, attention_dp_degree=2, batch_size=2)
    assert kv_cache_partition_spec(tc)["k"] == P(None, "dp", ("ep", "epx", "tp"), None, None)
    tc = TpuConfig(tp_degree=8, cp_degree=2, flash_decoding_enabled=True)
    assert kv_cache_partition_spec(tc)["k"] == P(None, None, ("ep", "epx", "tp"), "cp", None)
    assert kv_cache_partition_spec(None)["k"] == P(None, None, ("ep", "epx", "tp"), None, None)


@pytest.mark.parametrize(
    "tcfg_kwargs",
    [
        pytest.param(dict(attn_kernel_enabled=True), id="prefill-kernel"),
        pytest.param(
            dict(attn_kernel_enabled=True, attn_tkg_kernel_enabled=True),
            id="prefill+decode-kernel",
        ),
        pytest.param(
            dict(attn_kernel_enabled=True, cp_degree=2), id="kernel+cp2"
        ),
        pytest.param(
            dict(
                attn_kernel_enabled=True,
                attn_tkg_kernel_enabled=True,
                attention_dp_degree=2,
                batch_size=2,
            ),
            id="kernel+attn-dp2",
        ),
    ],
)
def test_flash_kernel_token_matching(tiny_hf_llama, tcfg_kwargs):
    """Pallas kernels (interpret mode on CPU) under the sharded dispatch must
    reproduce HF greedy tokens exactly on an 8-device mesh."""
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(hf_model, hf_cfg, **tcfg_kwargs)
    adapter = HuggingFaceGenerationAdapter(app)
    batch = tcfg_kwargs.get("batch_size", 1)
    prompt = np.tile(PROMPT, (batch, 1))
    expected = hf_greedy(hf_model, prompt, max_new_tokens=16)
    actual = adapter.generate(prompt, max_new_tokens=16)
    np.testing.assert_array_equal(actual, expected)


def test_dp_sampling_token_matching(tiny_hf_llama):
    """DataParallelSampler analog: batch-sharded sampling must emit the same
    greedy tokens (reference: modules/generation/sampling.py:469-569)."""
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg, batch_size=8,
        on_device_sampling_config=dict(dp_sampling=True),
    )
    adapter = HuggingFaceGenerationAdapter(app)
    prompt = np.tile(PROMPT, (8, 1))
    expected = hf_greedy(hf_model, prompt, max_new_tokens=12)
    actual = adapter.generate(prompt, max_new_tokens=12)
    np.testing.assert_array_equal(actual, expected)


def test_mlp_cp_degree_validation():
    from nxdi_tpu.config import TpuConfig
    from nxdi_tpu.parallel.policy import context_encoding_policy

    with pytest.raises(ValueError, match="must equal"):
        TpuConfig(tp_degree=8, mlp_cp_degree=2)  # partial degrees rejected
    # without SP the dedicated MLP-CP policy engages (mlp_hidden set)
    tc = TpuConfig(tp_degree=8, mlp_cp_degree=8)
    assert context_encoding_policy(tc).mlp_hidden is not None
    # with SP the whole stream is already S-sharded — subsumed, no extra spec
    tc_sp = TpuConfig(tp_degree=8, mlp_cp_degree=8, sequence_parallel_enabled=True)
    assert context_encoding_policy(tc_sp).mlp_hidden is None


def test_per_phase_hybrid_moe_token_matching():
    """hybrid_sharding_config (reference: HybridShardingConfig config.py:1060):
    CTE compiles TP-heavy, TKG EP-heavy over the duplicated expert copy, and
    greedy tokens must still exactly match HF CPU on the 8-device mesh."""
    import torch
    from transformers import MixtralConfig, MixtralForCausalLM

    from nxdi_tpu.models.mixtral import modeling_mixtral as mx
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    torch.manual_seed(0)
    hf_cfg = MixtralConfig(
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        vocab_size=256,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        num_local_experts=8,
        num_experts_per_tok=2,
    )
    hf_model = MixtralForCausalLM(hf_cfg).eval()
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    tcfg = TpuConfig(
        tp_degree=8,
        seq_len=64,
        max_context_length=32,
        batch_size=1,
        dtype="float32",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True,
        hybrid_sharding_config=dict(moe_cte_ep_degree=2, moe_tkg_ep_degree=8),
    )
    cfg = mx.MixtralInferenceConfig(tcfg, load_config=lambda: hf_cfg.to_dict())

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", cfg, model_family=mx)
    app.load()
    arch_cte = app.models["context_encoding_model"].arch
    arch_tkg = app.models["token_generation_model"].arch
    assert arch_cte.moe.phase == "prefill" and arch_tkg.moe.phase == "decode"

    prompt = np.array([[5, 9, 3, 17, 2, 8, 11, 42]], dtype=np.int64)
    expected = hf_greedy(hf_model, prompt, max_new_tokens=16)
    actual = HuggingFaceGenerationAdapter(app).generate(prompt, max_new_tokens=16)
    np.testing.assert_array_equal(actual, expected)


def test_attention_strategy_observability(tiny_hf_llama):
    """Each compiled program records which attention strategy it traced with
    (reference: FlashAttentionStrategy logging, attention_base.py:1330) — a
    silently-disengaged kernel becomes an assertable regression, not a perf
    mystery."""
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg, attn_kernel_enabled=True, attn_tkg_kernel_enabled=True
    )
    adapter = HuggingFaceGenerationAdapter(app)
    adapter.generate(np.tile(PROMPT, (1, 1)), max_new_tokens=4)
    strategies = {
        tag: prog.attention_strategies
        for tag, w in app.models.items()
        for prog in w._programs.values()
        if prog.attention_strategies
    }
    # prefill traced the flash kernel; decode the STACKED fused kernel
    # (round-4: reads the old cache from the layer stack via scalar-prefetch,
    # taking priority over the per-layer fused kernel)
    assert any("cte_flash_kernel" in s for s in strategies.values()), strategies
    assert any("tkg_fused_kernel_stacked" in s for s in strategies.values()), strategies

    # flash decoding (KV-S sharded cache) CANNOT run the single-shard kernels:
    # the fallback must be VISIBLE in the recorded strategies
    app2 = _build_app(
        hf_model, hf_cfg, attn_kernel_enabled=True, attn_tkg_kernel_enabled=True,
        cp_degree=2, flash_decoding_enabled=True, enable_bucketing=False,
    )
    adapter2 = HuggingFaceGenerationAdapter(app2)
    adapter2.generate(np.tile(PROMPT, (1, 1)), max_new_tokens=4)
    tkg = app2.models["token_generation_model"]
    tkg_strats = [p.attention_strategies for p in tkg._programs.values()
                  if p.attention_strategies]
    assert tkg_strats and all(
        "tkg_xla" in s or "tkg_two_part_xla" in s for s in tkg_strats
    ), tkg_strats


def test_segmented_pp2_deepseek_token_matching():
    """Heterogeneous segment stack (deepseek-V3 first_k_dense head + MoE
    rest) under pp2: each segment pipelines as its own GPipe lap (multi-lap
    virtual stages, run_decoder_layers pp branch); tokens must equal HF CPU
    greedy (reference analog: generation_minimax_m2_pp_demo.py)."""
    import torch
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    from nxdi_tpu.models.deepseek import modeling_deepseek as ds

    torch.manual_seed(0)
    hf_cfg = DeepseekV3Config(
        hidden_size=64, intermediate_size=128, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=8, vocab_size=256,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        q_lora_rank=32, kv_lora_rank=32, qk_rope_head_dim=8,
        qk_nope_head_dim=16, v_head_dim=16,
        first_k_dense_replace=2,  # 2 dense + 2 MoE: both segments pp2-even
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        n_group=4, topk_group=2, n_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, rope_scaling=None,
        tie_word_embeddings=False, eos_token_id=None,
    )
    hf_model = DeepseekV3ForCausalLM(hf_cfg).eval()
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}

    tcfg = TpuConfig(
        tp_degree=4, pp_degree=2, batch_size=2, seq_len=64,
        max_context_length=32, dtype="float32",
        on_device_sampling_config=OnDeviceSamplingConfig(), skip_warmup=True,
    )
    cfg = ds.DeepseekInferenceConfig(tcfg, load_config=lambda: hf_cfg.to_dict())

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", cfg, model_family=ds)
    app.load()
    prompt = np.tile(PROMPT, (2, 1))
    expected = hf_greedy(hf_model, prompt, max_new_tokens=12)
    actual = HuggingFaceGenerationAdapter(app).generate(prompt, max_new_tokens=12)
    np.testing.assert_array_equal(actual, expected)


def test_collect_hidden_under_pp_matches_tp(tiny_hf_llama):
    """EAGLE3 aux taps / tensor capture need per-layer hiddens; under pp the
    stages bank their layers' hiddens per microbatch and the pp out-spec
    reassembles global layer order — captured tensors must match a plain tp
    run bit-for-bit."""
    hf_model, hf_cfg = tiny_hf_llama
    from nxdi_tpu.config import TensorCaptureConfig

    caps = {}
    for name, kw in (
        ("tp", dict(tp_degree=8)),
        ("pp", dict(tp_degree=4, pp_degree=2)),
    ):
        app = _build_app(
            hf_model, hf_cfg, batch_size=2,
            tensor_capture_config=TensorCaptureConfig(
                capture_points=("layer_hiddens",)
            ),
            **kw,
        )
        prompt = np.tile(PROMPT, (2, 1)).astype(np.int32)
        pos = np.tile(np.arange(prompt.shape[1], dtype=np.int32), (2, 1))
        out = app.forward(
            prompt, pos,
            last_token_index=np.full((2,), prompt.shape[1] - 1, np.int32),
        )
        caps[name] = np.asarray(out["captured"]["layer_hiddens"])
    assert caps["tp"].shape == caps["pp"].shape
    np.testing.assert_allclose(caps["tp"], caps["pp"], rtol=2e-5, atol=2e-5)
