"""K-EXAONE (``model_type: exaone_moe``) — sliding-window and global GQA layers
in EXAONE 4.0's post-norm residual path, with routed experts beside a shared
one.

Per layer (``layer_types`` / ``sliding_windows`` / ``mlp_layer_types``, read
up to ``num_hidden_layers``):

    q, k, v = W_q x, W_k x, W_v x;   q, k <- RMSNorm_head(q), RMSNorm_head(k)
    WINDOW layer: RoPE on all channels, query i sees keys 0 <= i - j < window
    FULL layer:   no RoPE (global NoPE), causal
    x <- x + RMSNorm(W_o attention(q, k, v))
    x <- x + RMSNorm(ffn(x))

No input norm: each sublayer's OUTPUT is normed before the residual add (the
``post_block_norm`` ordering, as ``models/exaone4``). ``ffn`` is a SwiGLU of
``intermediate_size`` on a ``dense`` layer; on a ``sparse`` one the sigmoid
router's top k chosen over ``scores + e_score_correction_bias``, weights from
the scores alone, renormalised and times ``routed_scaling_factor``, plus the
shared expert.

One chip's share of an expert-parallel deployment (``num_experts_total`` in
the config): the router keeps its published width, this chip's tree holds
``num_experts`` experts from ``first_routed_expert`` on (ops/moe.py
``MoEArch.held_experts``); on a share the post-FFN norm takes the held
experts' partial sum and the shared expert. The multi-token-prediction layer
is no part of the served pass.

The walk over the two kinds of layer, their caches and the application are
``models/window_full.py``'s, shared with MiMo-V2-Flash.

Params keep the ``post_block_norm`` keys (``models/base.py``):
``input_layernorm`` holds the post-ATTENTION norm (HF
``post_attention_layernorm``), ``post_attention_layernorm`` the post-FFN norm
(HF ``post_feedforward_layernorm``); ``attn.q_norm`` / ``attn.k_norm`` a
head's width each.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from nxdi_tpu.analysis.costs import resolve_chip
from nxdi_tpu.config import InferenceConfig
from nxdi_tpu.models import dense, window_full
from nxdi_tpu.models.base import DecoderArch
from nxdi_tpu.models.window_full import FULL, WINDOW, WindowFullArch
from nxdi_tpu.ops.moe import MoEArch, convert_hf_experts, moe_parallel_fields
from nxdi_tpu.ops.rope import inv_freq_from_hf_config
from nxdi_tpu.parallel import gqa

#: the per-layer lists of the published config, read up to num_hidden_layers
LAYER_LISTS = ("layer_types", "mlp_layer_types", "sliding_windows")


class ExaoneMoeInferenceConfig(dense.DenseInferenceConfig):
    REQUIRED = [
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size", "intermediate_size", "rms_norm_eps", "layer_types",
        "mlp_layer_types", "sliding_window", "num_experts", "num_experts_per_tok",
        "moe_intermediate_size",
    ]

    def add_derived_config(self):
        n = self.num_hidden_layers
        for key in LAYER_LISTS:
            if not hasattr(self, key):
                continue
            if len(getattr(self, key)) < n:
                raise ValueError(f"{key} has {len(getattr(self, key))} entries for {n} layers")
            setattr(self, key, list(getattr(self, key))[:n])
        spans = getattr(self, "sliding_windows", None)
        for i, kind in enumerate(self.layer_types):
            span = self.sliding_window if kind == "sliding_attention" else 0
            if kind not in ("sliding_attention", "full_attention") or (spans and int(spans[i]) != span):
                raise NotImplementedError(
                    f"layer {i}: {kind} with window {spans[i] if spans else None}; exaone_moe has one "
                    f"window of {self.sliding_window} on sliding layers and none on full ones")
        rope = getattr(self, "rope_parameters", None) or {}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError("exaone_moe has plain RoPE only")
        if "rope_theta" in rope:
            self.rope_theta = rope["rope_theta"]
        super().add_derived_config()


def layer_kinds(config) -> List[str]:
    return [WINDOW if t == "sliding_attention" else FULL for t in config.layer_types]


def layer_routed(config) -> List[bool]:
    return [t == "sparse" for t in config.mlp_layer_types]


def _moe_arch(config: InferenceConfig) -> MoEArch:
    # a share: ``num_experts`` counts the experts held here, the router keeps
    # the published width (``num_experts_total``)
    if str(getattr(config, "scoring_func", "sigmoid")) != "sigmoid":
        raise NotImplementedError("exaone_moe has the sigmoid router only")
    held = config.num_experts
    E = getattr(config, "num_experts_total", None) or held
    shared = int(getattr(config, "num_shared_experts", 0) or 0)
    n_group = getattr(config, "n_group", None) or 1
    return MoEArch(
        num_experts=E,
        top_k=config.num_experts_per_tok,
        intermediate_size=config.moe_intermediate_size,
        hidden_act=getattr(config, "hidden_act", "silu"),
        norm_topk_prob=bool(getattr(config, "norm_topk_prob", True)),
        sigmoid_routing=True,
        n_group=n_group if n_group > 1 else None,
        topk_group=getattr(config, "topk_group", None) if n_group > 1 else None,
        routed_scaling=float(getattr(config, "routed_scaling_factor", None) or 1.0),
        # DeepSeek-V3's noaux_tc, which n_group / topk_group / routed_scaling_factor
        # name: the top k over scores + a learned bias, the weights from the scores
        correction_bias=True,
        shared_expert_intermediate_size=shared * config.moe_intermediate_size or None,
        held_experts=held if held != E else None,
        first_held=int(getattr(config, "first_routed_expert", 0) or 0),
        # the chip a share is built for sizes its slots (ops/moe.py compact_capacity)
        ridge_rows=resolve_chip(config.tpu_config).ridge_rows if held != E else None,
        **moe_parallel_fields(config.tpu_config, E),
    )


def build_arch(config: InferenceConfig, **overrides) -> WindowFullArch:
    hd = config.head_dim
    plan = gqa.plan_gqa_sharding(
        config.tpu_config.tp_degree, config.num_attention_heads, config.num_key_value_heads)
    kinds = layer_kinds(config)
    moe = _moe_arch(config)

    def kind_arch(kind: str) -> DecoderArch:
        return dense.build_arch(
            config,
            num_layers=kinds.count(kind),
            num_attention_heads=plan.target_heads,
            num_kv_heads=plan.target_kv,
            head_dim=hd,
            sliding_window=config.sliding_window if kind == WINDOW else None,
            no_rope=kind == FULL,  # global NoPE
            post_block_norm=True,
            qk_norm=True,
            attention_scale=float(hd) ** -0.5,
            moe=moe,
            **overrides,
        )

    return window_full.build(kind_arch(FULL), kind_arch(WINDOW), kinds, layer_routed(config))


def build_inv_freq(config: InferenceConfig) -> Dict[str, np.ndarray]:
    return {
        FULL: np.zeros((1,), np.float32),  # read by nothing: the full layers have no rope
        WINDOW: inv_freq_from_hf_config(config.head_dim, config.rope_theta, None),
    }


# ---------------------------------------------------------------------------
# Conversion / specs / structs
# ---------------------------------------------------------------------------


def _convert_layer(state_dict, config, arch: WindowFullArch, i: int, kind: str, routed: bool):
    ta = arch.of(kind)
    plan = gqa.plan_gqa_sharding(
        config.tpu_config.tp_degree, config.num_attention_heads, config.num_key_value_heads)
    D = ta.head_dim
    dt = dense.np_dtype(ta.dtype)
    cast = lambda x: np.asarray(x, dt)  # noqa: E731
    pre = f"model.layers.{i}."

    def get(*names):
        for name in names:
            for k in (pre + name, pre.replace("model.", "", 1) + name):
                if k in state_dict:
                    return np.asarray(state_dict[k])
        raise KeyError(pre + names[0])

    layer = {
        # the post_block_norm keys: the post-attention norm, then the post-FFN one
        "input_layernorm": cast(get("post_attention_layernorm.weight")),
        "post_attention_layernorm": cast(get("post_feedforward_layernorm.weight")),
        "attn": {
            "q_proj": {"w": cast(gqa.convert_q(get("self_attn.q_proj.weight"), D, plan).T)},
            "k_proj": {"w": cast(gqa.convert_kv(get("self_attn.k_proj.weight"), D, plan).T)},
            "v_proj": {"w": cast(gqa.convert_kv(get("self_attn.v_proj.weight"), D, plan).T)},
            "o_proj": {"w": cast(gqa.convert_o(get("self_attn.o_proj.weight"), D, plan).T)},
            "q_norm": cast(get("self_attn.q_norm.weight")),
            "k_norm": cast(get("self_attn.k_norm.weight")),
        },
    }
    if routed:
        moe = ta.moe
        first = moe.first_held  # a share converts its own experts alone
        layer["moe"] = convert_hf_experts(
            get, cast, moe.experts_here, "mlp.gate.weight",
            lambda j, proj: f"mlp.experts.{first + j}.{proj}_proj.weight",
        )
        # selection only: float32, as the scores are
        layer["moe"]["router"]["e_bias"] = np.asarray(
            get("mlp.e_score_correction_bias", "mlp.gate.e_score_correction_bias"), np.float32)
        if moe.shared_expert_intermediate_size:
            layer["moe"]["shared_expert"] = {
                f"{p}_proj": {"w": cast(get(f"mlp.shared_experts.{p}_proj.weight").T)}
                for p in ("gate", "up", "down")
            }
    else:
        layer["mlp"] = {f"{p}_proj": {"w": cast(get(f"mlp.{p}_proj.weight").T)} for p in ("gate", "up", "down")}
    return layer


def convert_hf_state_dict(state_dict: Dict[str, np.ndarray], config: InferenceConfig) -> Dict[str, Any]:
    arch = build_arch(config)
    return window_full.convert_segments(
        state_dict, arch,
        lambda i, kind, routed: _convert_layer(state_dict, config, arch, i, kind, routed),
    )


def param_specs(config: InferenceConfig):
    return window_full.param_specs(build_arch(config))


def param_shape_struct(config: InferenceConfig):
    return window_full.param_shape_struct(build_arch(config))


class ExaoneMoeForCausalLM:
    def __new__(cls, *args, **kwargs):
        from nxdi_tpu.models.exaone_moe.application import ExaoneMoeApplication

        return ExaoneMoeApplication(*args, **kwargs)


def __getattr__(name):
    # lazy APPLICATION_CLS: application.py imports this module
    if name == "APPLICATION_CLS":
        from nxdi_tpu.models.exaone_moe.application import ExaoneMoeApplication

        return ExaoneMoeApplication
    raise AttributeError(name)
