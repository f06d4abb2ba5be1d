"""Model-family registry: HF ``model_type`` -> family module + config class.

The analog of the reference CLI's MODEL_TYPES table (inference_demo.py:53).
A "family module" exposes: ``build_arch``, ``build_inv_freq``,
``convert_hf_state_dict``, ``param_specs``, and a ``*InferenceConfig`` class.
"""

from __future__ import annotations

import importlib
from typing import Dict, Tuple

_REGISTRY: Dict[str, Tuple[str, str]] = {
    # model_type -> (module path, config class name)
    "llama": ("nxdi_tpu.models.llama.modeling_llama", "LlamaInferenceConfig"),
    "qwen2": ("nxdi_tpu.models.qwen2.modeling_qwen2", "Qwen2InferenceConfig"),
    "qwen3": ("nxdi_tpu.models.qwen3.modeling_qwen3", "Qwen3InferenceConfig"),
    "mistral": ("nxdi_tpu.models.mistral.modeling_mistral", "MistralInferenceConfig"),
    "mixtral": ("nxdi_tpu.models.mixtral.modeling_mixtral", "MixtralInferenceConfig"),
    "qwen3_moe": ("nxdi_tpu.models.qwen3_moe.modeling_qwen3_moe", "Qwen3MoeInferenceConfig"),
    "gemma3": (
        "nxdi_tpu.models.gemma3.modeling_gemma3_vision",
        "Gemma3VisionInferenceConfig",
    ),
    "gemma3_text": ("nxdi_tpu.models.gemma3.modeling_gemma3", "Gemma3InferenceConfig"),
    "pixtral": ("nxdi_tpu.models.pixtral.modeling_pixtral", "PixtralInferenceConfig"),
    "mistral3": ("nxdi_tpu.models.pixtral.modeling_pixtral", "Mistral3InferenceConfig"),
    "ovis2": ("nxdi_tpu.models.ovis2.modeling_ovis2", "Ovis2InferenceConfig"),
    "dbrx": ("nxdi_tpu.models.dbrx.modeling_dbrx", "DbrxInferenceConfig"),
    "gpt_oss": ("nxdi_tpu.models.gpt_oss.modeling_gpt_oss", "GptOssInferenceConfig"),
    "deepseek_v3": ("nxdi_tpu.models.deepseek.modeling_deepseek", "DeepseekInferenceConfig"),
    "deepseek": ("nxdi_tpu.models.deepseek.modeling_deepseek", "DeepseekInferenceConfig"),
    "pangu_ultra_moe": (
        "nxdi_tpu.models.deepseek.modeling_deepseek",
        "PanguUltraMoeInferenceConfig",
    ),
    "llama4": ("nxdi_tpu.models.llama4.modeling_llama4", "Llama4InferenceConfig"),
    "llama4_text": ("nxdi_tpu.models.llama4.modeling_llama4", "Llama4InferenceConfig"),
    "llava": ("nxdi_tpu.models.llava.modeling_llava", "LlavaInferenceConfig"),
    "mllama": ("nxdi_tpu.models.mllama.modeling_mllama", "MllamaInferenceConfig"),
    "qwen2_vl": ("nxdi_tpu.models.qwen2_vl.modeling_qwen2_vl", "Qwen2VLInferenceConfig"),
    "qwen3_vl": ("nxdi_tpu.models.qwen3_vl.modeling_qwen3_vl", "Qwen3VLInferenceConfig"),
    "qwen2_5_vl": ("nxdi_tpu.models.qwen2_5_vl.modeling_qwen2_5_vl", "Qwen2_5_VLInferenceConfig"),
    "minimax_m2": ("nxdi_tpu.models.minimax_m2.modeling_minimax_m2", "MiniMaxM2InferenceConfig"),
    "mimo_v2": ("nxdi_tpu.models.mimo_v2.modeling_mimo_v2", "MiMoV2InferenceConfig"),
    # the published model_type of MiMo-V2-Flash (MiMo-V2.5 says "mimo_v2")
    "mimo_v2_flash": ("nxdi_tpu.models.mimo_v2.modeling_mimo_v2", "MiMoV2InferenceConfig"),
    "olmo2": ("nxdi_tpu.models.olmo2.modeling_olmo2", "Olmo2InferenceConfig"),
    "granite": ("nxdi_tpu.models.granite.modeling_granite", "GraniteInferenceConfig"),
    "smollm3": ("nxdi_tpu.models.smollm3.modeling_smollm3", "SmolLM3InferenceConfig"),
    "gpt2": ("nxdi_tpu.models.gpt2.modeling_gpt2", "GPT2InferenceConfig"),
    "gemma2": ("nxdi_tpu.models.gemma2.modeling_gemma2", "Gemma2InferenceConfig"),
    "phi3": ("nxdi_tpu.models.phi3.modeling_phi3", "Phi3InferenceConfig"),
    "qwen3_next": (
        "nxdi_tpu.models.qwen3_next.modeling_qwen3_next",
        "Qwen3NextInferenceConfig",
    ),
    "recurrent_gemma": (
        "nxdi_tpu.models.recurrentgemma.modeling_recurrentgemma",
        "RecurrentGemmaInferenceConfig",
    ),
    "recurrentgemma": (
        "nxdi_tpu.models.recurrentgemma.modeling_recurrentgemma",
        "RecurrentGemmaInferenceConfig",
    ),
    "qwen2_5_omni": (
        "nxdi_tpu.models.qwen2_5_omni.modeling_qwen2_5_omni",
        "Qwen2_5OmniInferenceConfig",
    ),
    "phimoe": (
        "nxdi_tpu.models.phimoe.modeling_phimoe",
        "PhimoeInferenceConfig",
    ),
    "lfm2": (
        "nxdi_tpu.models.lfm2.modeling_lfm2",
        "Lfm2InferenceConfig",
    ),
    "qwen2_5_omni_thinker": (
        "nxdi_tpu.models.qwen2_5_omni.modeling_qwen2_5_omni",
        "Qwen2_5OmniInferenceConfig",
    ),
    "falcon_h1": (
        "nxdi_tpu.models.falcon_h1.modeling_falcon_h1",
        "FalconH1InferenceConfig",
    ),
    "ernie4_5": (
        "nxdi_tpu.models.ernie4_5.modeling_ernie4_5",
        "Ernie4_5InferenceConfig",
    ),
    "seed_oss": (
        "nxdi_tpu.models.seed_oss.modeling_seed_oss",
        "SeedOssInferenceConfig",
    ),
    "helium": (
        "nxdi_tpu.models.helium.modeling_helium",
        "HeliumInferenceConfig",
    ),
    "starcoder2": (
        "nxdi_tpu.models.starcoder2.modeling_starcoder2",
        "Starcoder2InferenceConfig",
    ),
    "stablelm": (
        "nxdi_tpu.models.stablelm.modeling_stablelm",
        "StableLmInferenceConfig",
    ),
    "glm4": (
        "nxdi_tpu.models.glm4.modeling_glm4",
        "Glm4InferenceConfig",
    ),
    "exaone4": (
        "nxdi_tpu.models.exaone4.modeling_exaone4",
        "Exaone4InferenceConfig",
    ),
    "olmo3": (
        "nxdi_tpu.models.olmo3.modeling_olmo3",
        "Olmo3InferenceConfig",
    ),
    "cohere2": (
        "nxdi_tpu.models.cohere2.modeling_cohere2",
        "Cohere2InferenceConfig",
    ),
    "gpt_neox": (
        "nxdi_tpu.models.gpt_neox.modeling_gpt_neox",
        "GPTNeoXInferenceConfig",
    ),
    "ministral": (
        "nxdi_tpu.models.ministral.modeling_ministral",
        "MinistralInferenceConfig",
    ),
    "hunyuan_v1_dense": (
        "nxdi_tpu.models.hunyuan.modeling_hunyuan",
        "HunYuanInferenceConfig",
    ),
    "arcee": ("nxdi_tpu.models.arcee.modeling_arcee", "ArceeInferenceConfig"),
    "gemma": ("nxdi_tpu.models.gemma.modeling_gemma", "GemmaInferenceConfig"),
    "vaultgemma": (
        "nxdi_tpu.models.vaultgemma.modeling_vaultgemma",
        "VaultGemmaInferenceConfig",
    ),
    "opt": ("nxdi_tpu.models.opt.modeling_opt", "OPTInferenceConfig"),
    "biogpt": ("nxdi_tpu.models.biogpt.modeling_biogpt", "BioGptInferenceConfig"),
    "xglm": ("nxdi_tpu.models.xglm.modeling_xglm", "XGLMInferenceConfig"),
    "gpt_bigcode": (
        "nxdi_tpu.models.gpt_bigcode.modeling_gpt_bigcode",
        "GPTBigCodeInferenceConfig",
    ),
    "falcon": ("nxdi_tpu.models.falcon.modeling_falcon", "FalconInferenceConfig"),
    "persimmon": (
        "nxdi_tpu.models.persimmon.modeling_persimmon",
        "PersimmonInferenceConfig",
    ),
    "phi": ("nxdi_tpu.models.phi.modeling_phi", "PhiInferenceConfig"),
    "apertus": (
        "nxdi_tpu.models.apertus.modeling_apertus",
        "ApertusInferenceConfig",
    ),
    "janus": ("nxdi_tpu.models.janus.modeling_janus", "JanusInferenceConfig"),
    "idefics": (
        "nxdi_tpu.models.idefics.modeling_idefics",
        "IdeficsInferenceConfig",
    ),
    "minicpm": ("nxdi_tpu.models.minicpm.modeling_minicpm", "MiniCPMInferenceConfig"),
    "minicpm4": ("nxdi_tpu.models.minicpm.modeling_minicpm", "MiniCPMInferenceConfig"),
    "minicpm_sala": (
        "nxdi_tpu.models.minicpm_sala.modeling_minicpm_sala",
        "MiniCPMSALAInferenceConfig",
    ),
    "internlm3": (
        "nxdi_tpu.models.internlm3.modeling_internlm3",
        "InternLM3InferenceConfig",
    ),
    "orion": ("nxdi_tpu.models.orion.modeling_orion", "OrionInferenceConfig"),
    "afmoe": ("nxdi_tpu.models.afmoe.modeling_afmoe", "AfmoeInferenceConfig"),
}


def register(model_type: str, module_path: str, config_cls_name: str) -> None:
    _REGISTRY[model_type] = (module_path, config_cls_name)


def get_family(model_type: str):
    if model_type not in _REGISTRY:
        raise KeyError(
            f"Unknown model_type {model_type!r}; registered: {sorted(_REGISTRY)}"
        )
    module_path, cfg_name = _REGISTRY[model_type]
    module = importlib.import_module(module_path)
    return module, getattr(module, cfg_name)


def known_model_types():
    return sorted(_REGISTRY)
