"""Quantized end-to-end generation on tiny random llama (reference analog:
inference_demo --quantized + quantized accuracy runs, inference_demo.py:170-199,
application_base.py:744-797)."""

import jax
import numpy as np
import pytest

from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
from nxdi_tpu.generation.hf_adapter import HuggingFaceGenerationAdapter
from nxdi_tpu.models.llama import modeling_llama as ml
from nxdi_tpu.runtime.application import TpuModelForCausalLM
from nxdi_tpu.utils.accuracy import hf_greedy_generate as hf_greedy


def build_app(hf_model, hf_cfg, **tpu_kwargs):
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    defaults = dict(
        tp_degree=1,
        seq_len=64,
        max_context_length=32,
        batch_size=1,
        dtype="float32",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True,
    )
    defaults.update(tpu_kwargs)
    tcfg = TpuConfig(**defaults)
    cfg = ml.LlamaInferenceConfig(tcfg, load_config=lambda: hf_cfg.to_dict())

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", cfg, model_family=ml)
    app.load()
    return app


def _dequantized_clone(hf_model, scheme):
    """Golden oracle: the HF model with every decoder linear weight replaced by
    dequantize(quantize(w)) under the same scheme — our quantized app must match
    it token-exactly (isolates the machinery from quantization noise, which on
    random tiny nets flips near-uniform argmaxes)."""
    import copy

    import torch

    from nxdi_tpu.ops import quantization as q

    model = copy.deepcopy(hf_model)
    for layer in model.model.layers:
        mods = [
            layer.self_attn.q_proj, layer.self_attn.k_proj,
            layer.self_attn.v_proj, layer.self_attn.o_proj,
            layer.mlp.gate_proj, layer.mlp.up_proj, layer.mlp.down_proj,
        ]
        for m in mods:
            w = m.weight.detach().numpy().T  # (in, out) layout
            qw, scale = q.quantize_array(w, "int8", scheme)
            m.weight.data = torch.from_numpy(q.dequantize_array(qw, scale).T.copy())
    return model


@pytest.mark.parametrize("tp_degree", [1, 8])
@pytest.mark.parametrize(
    "scheme", ["per_tensor_symmetric", "per_channel_symmetric"]
)
def test_int8_weight_quant_token_matching(tiny_hf_llama, tp_degree, scheme):
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(
        hf_model, hf_cfg, tp_degree=tp_degree, quantized=True, quantization_type=scheme
    )
    adapter = HuggingFaceGenerationAdapter(app)

    prompt = np.array([[5, 9, 3, 17, 2, 8, 11, 42]], dtype=np.int64)
    expected = hf_greedy(_dequantized_clone(hf_model, scheme), prompt, max_new_tokens=8)
    actual = adapter.generate(prompt, max_new_tokens=8)
    np.testing.assert_array_equal(actual, expected)


def test_fp8_weight_quant_runs(tiny_hf_llama):
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(
        hf_model, hf_cfg, quantized=True, quantization_dtype="f8e4m3"
    )
    adapter = HuggingFaceGenerationAdapter(app)
    prompt = np.array([[5, 9, 3, 17]], dtype=np.int64)
    out = adapter.generate(prompt, max_new_tokens=4)
    assert out.shape == (1, 8)


def test_dynamic_activation_quant_runs(tiny_hf_llama):
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(
        hf_model,
        hf_cfg,
        quantized=True,
        activation_quantization_type="dynamic",
    )
    adapter = HuggingFaceGenerationAdapter(app)
    prompt = np.array([[5, 9, 3, 17]], dtype=np.int64)
    out = adapter.generate(prompt, max_new_tokens=4)
    assert out.shape == (1, 8)


def test_offline_quantized_checkpoint_roundtrip(tiny_hf_llama, tmp_path):
    """save_quantized_state_dict -> reload via quantized_checkpoints_path gives
    identical generations to online quantization."""
    hf_model, hf_cfg = tiny_hf_llama
    qdir = str(tmp_path / "quantized")

    app_online = build_app(hf_model, hf_cfg, quantized=True)
    app_online.save_quantized_state_dict(qdir)

    app_offline = build_app(
        hf_model, hf_cfg, quantized=True, quantized_checkpoints_path=qdir
    )
    prompt = np.array([[5, 9, 3, 17, 2, 8]], dtype=np.int64)
    out_a = HuggingFaceGenerationAdapter(app_online).generate(prompt, max_new_tokens=6)
    out_b = HuggingFaceGenerationAdapter(app_offline).generate(prompt, max_new_tokens=6)
    np.testing.assert_array_equal(out_a, out_b)


@pytest.mark.parametrize("tp_degree", [1, 8])
def test_quantized_moe_runs(tp_degree):
    """MoE + quantized: expert weights go int8 while router/gates stay full
    precision (DEFAULT_MODULES_TO_NOT_CONVERT) — regression for the router
    KeyError/spec-mismatch class of bug."""
    import torch
    from transformers import MixtralConfig, MixtralForCausalLM

    from nxdi_tpu.models.registry import get_family

    torch.manual_seed(0)
    hf_cfg = MixtralConfig(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        num_local_experts=8, num_experts_per_tok=2,
    )
    hf_model = MixtralForCausalLM(hf_cfg).eval()
    family, cfg_cls = get_family("mixtral")
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    tcfg = TpuConfig(
        tp_degree=tp_degree, seq_len=64, max_context_length=32, batch_size=1,
        dtype="float32", on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True, quantized=True,
    )
    cfg = cfg_cls(tcfg, load_config=lambda: hf_cfg.to_dict())

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", cfg, model_family=family)
    app.load()
    # router must remain unquantized; experts must be quantized
    layer_params = app.params["layers"]
    assert "w" in jax.tree_util.tree_map(lambda x: 0, layer_params["moe"]["router"])
    assert "qw" in layer_params["moe"]["experts"]["gate_proj"]

    prompt = np.array([[5, 9, 3, 17, 2, 8]], dtype=np.int64)
    out = HuggingFaceGenerationAdapter(app).generate(prompt, max_new_tokens=4)
    assert out.shape == (1, 10)


def test_activation_quant_config_validation():
    """Unsupported activation-quant combos must raise, not silently no-op."""
    with pytest.raises(ValueError):
        TpuConfig(activation_quantization_type="dynamic")  # quantized=False
    with pytest.raises(ValueError):
        TpuConfig(
            quantized=True, quantization_dtype="f8e4m3",
            activation_quantization_type="dynamic",
        )
    with pytest.raises(ValueError):  # static also needs the int8 path
        TpuConfig(
            quantized=True, quantization_dtype="f8e4m3",
            activation_quantization_type="static",
        )
    with pytest.raises(ValueError):
        TpuConfig(quantized=True, activation_quantization_type="bogus")
    # static + int8 is valid; the reference's upper-case spelling normalizes
    assert (
        TpuConfig(quantized=True, activation_quantization_type="STATIC")
        .activation_quantization_type == "static"
    )


def test_static_activation_quant_linear_mechanics():
    """quantized_linear(act_quant='static') must match the hand computation
    exactly: round(x/input_scale) clipped, int8 MXU dot, double rescale."""
    from nxdi_tpu.ops import quantization as q

    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 16)).astype(np.float32)
    w = rng.standard_normal((16, 8)).astype(np.float32)
    qw, scale = q.quantize_array(w, "int8", "per_channel_symmetric")
    in_s = np.float32(np.abs(x).max() / 127.0)
    p = {"qw": jax.numpy.asarray(qw), "scale": jax.numpy.asarray(scale),
         "input_scale": jax.numpy.asarray(in_s)}
    actual = np.asarray(q.quantized_linear(jax.numpy.asarray(x), p, act_quant="static"))

    qx = np.clip(np.round(x / in_s), -127, 127).astype(np.int32)
    expected = (qx @ qw.astype(np.int32)).astype(np.float32) * in_s * scale.squeeze(-2)
    np.testing.assert_allclose(actual, expected, rtol=1e-6)


def test_static_activation_quant_calibrated_e2e(tiny_hf_llama, tmp_path):
    """dynamic-mode calibration -> static serving: calibrated input scales
    attach to every quantized linear, the static app generates, and the
    quantized-artifact round trip preserves the scales exactly."""
    from nxdi_tpu.ops import quantization as q

    hf_model, hf_cfg = tiny_hf_llama
    app_dyn = build_app(
        hf_model, hf_cfg, quantized=True,
        activation_quantization_type="dynamic",
    )
    prompt = np.array([[5, 9, 3, 17, 2, 8]], dtype=np.int64)
    calib = q.calibrate_app_input_scales(app_dyn, [prompt])

    # every quantized linear gained a positive calibrated scale
    n_scales = 0

    def count(tree):
        nonlocal n_scales
        if isinstance(tree, dict):
            if "qw" in tree:
                assert "input_scale" in tree, "uncalibrated quantized linear"
                assert (np.asarray(tree["input_scale"]) > 0).all()
                # calibration must have replaced the identity placeholder
                assert (np.asarray(tree["input_scale"]) != 1.0).any()
                n_scales += 1
            else:
                for v in tree.values():
                    count(v)

    count(calib)
    assert n_scales > 0

    class AppS(TpuModelForCausalLM):
        def build_params(self):
            return calib

    tcfg = TpuConfig(
        tp_degree=1, seq_len=64, max_context_length=32, batch_size=1,
        dtype="float32", on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True, quantized=True,
        activation_quantization_type="static",
    )
    cfg = ml.LlamaInferenceConfig(tcfg, load_config=lambda: hf_cfg.to_dict())
    app_s = AppS("<memory>", cfg, model_family=ml)
    app_s.load()
    out = HuggingFaceGenerationAdapter(app_s).generate(prompt, max_new_tokens=6)
    assert out.shape == (1, 12)
    assert (out >= 0).all()

    # artifact round trip: saved scales reload bit-identically and the
    # offline app generates the same tokens
    qdir = str(tmp_path / "static_q")
    app_s.save_quantized_state_dict(qdir)
    app_off = build_app(
        hf_model, hf_cfg, quantized=True,
        activation_quantization_type="static",
        quantized_checkpoints_path=qdir,
    )
    out_b = HuggingFaceGenerationAdapter(app_off).generate(prompt, max_new_tokens=6)
    np.testing.assert_array_equal(out, out_b)


def test_kv_cache_fp8_quant(tiny_hf_llama):
    """fp8 KV cache (reference: kv_cache_manager.py:642-692 direct-cast)."""
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(hf_model, hf_cfg, kv_cache_quant=True)
    assert app.kv_cache["k"].dtype.name.startswith("float8")
    adapter = HuggingFaceGenerationAdapter(app)
    prompt = np.array([[5, 9, 3, 17, 2, 8, 11, 42]], dtype=np.int64)
    expected = hf_greedy(hf_model, prompt, max_new_tokens=8)
    actual = adapter.generate(prompt, max_new_tokens=8)
    match = (actual == expected).mean()
    assert match >= 0.75, (actual, expected)


def test_kv_cache_fp8_per_tensor_scaled(tiny_hf_llama):
    """Scaled fp8 KV cache (scale_mode="per_tensor"): values stored as v/scale
    and rescaled on read (reference: calibrated scale buffers,
    kv_cache_manager.py:642-692). With a scale the quantized rollout must
    still track the f32 golden; an absurd scale must change tokens (proving
    the scale actually flows through the compiled program)."""
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(
        hf_model, hf_cfg,
        kv_quant_config={"dtype": "float8_e4m3", "scale_mode": "per_tensor",
                         "k_scale": 0.5, "v_scale": 0.5},
    )
    assert app.kv_cache["k"].dtype.name.startswith("float8")
    adapter = HuggingFaceGenerationAdapter(app)
    prompt = np.array([[5, 9, 3, 17, 2, 8, 11, 42]], dtype=np.int64)
    expected = hf_greedy(hf_model, prompt, max_new_tokens=8)
    actual = adapter.generate(prompt, max_new_tokens=8)
    match = (actual == expected).mean()
    assert match >= 0.75, (actual, expected)

    # degenerate scale wrecks the cache contents -> rollout must diverge,
    # i.e. the scale is not a silent no-op
    app_bad = build_app(
        hf_model, hf_cfg,
        kv_quant_config={"dtype": "float8_e4m3", "scale_mode": "per_tensor",
                         "k_scale": 1e-6, "v_scale": 1e-6},
    )
    bad = HuggingFaceGenerationAdapter(app_bad).generate(prompt, max_new_tokens=8)
    assert not np.array_equal(bad, expected)


def test_kv_quant_scale_mode_validation():
    from nxdi_tpu.config import KVQuantizationConfig

    with pytest.raises(ValueError, match="scale_mode"):
        KVQuantizationConfig(scale_mode="per_channel")
    with pytest.raises(ValueError, match="per_tensor"):
        KVQuantizationConfig(scale_mode="direct_cast", k_scale=0.5)


def test_mxfp4_e2e_rollout(tiny_hf_llama):
    """MXFP4 weights produce a sane rollout and differ from the base model
    (reference pairing: gpt-oss MXFP4 — here proven on the shared linear path)."""
    import numpy as np

    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(hf_model, hf_cfg, quantized=True, quantization_dtype="mxfp4")
    from nxdi_tpu.generation.hf_adapter import HuggingFaceGenerationAdapter

    adapter = HuggingFaceGenerationAdapter(app)
    prompt = np.array([[5, 9, 3, 17, 2, 8, 11, 42]], dtype=np.int64)
    out = adapter.generate(prompt, max_new_tokens=8)
    assert out.shape == (1, 16)
    assert (out >= 0).all() and (out < hf_cfg.vocab_size).all()
