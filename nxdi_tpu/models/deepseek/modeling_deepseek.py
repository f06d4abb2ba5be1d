"""Deepseek (V2/V3 lineage) family — Multi-head Latent Attention + V3 MoE.

Reference: models/deepseek/modeling_deepseek.py (493 LoC; MLA attention with
q-LoRA, compressed kv latents, yarn rope from rope_util.py) and the contrib
DeepSeek-V3 tree (sigmoid-scored grouped top-k router with learned correction
bias, shared experts, first_k_dense_replace leading dense layers). The
attention lives in ops/mla.py, designed around a latent KV cache (the
reference caches expanded per-head K/V; the latent cache is the TPU-native
choice — see the ops/mla.py docstring). V3 routing semantics live in
ops/moe.py:route_topk (sigmoid_routing / n_group / topk_group /
correction_bias); the dense-head + MoE-tail layer mix rides the segmented
layer scan (models/base.py run_decoder_layers).

``pangu_ultra_moe`` (openPangu-Ultra-MoE) is the same stack with four norms a
layer (``sandwich_norm``: the block outputs are normed before the residual
add, under the published names ``pre_mlp_layernorm``/``post_mlp_layernorm``),
a sigmoid router without selection bias or groups, plain RoPE in rotate-half
order, and no multi-token-prediction module in the served forward pass:
:class:`PanguUltraMoeInferenceConfig` states those, the code below branches
on what the config says.

One chip's share of an expert-parallel deployment (``n_routed_experts_total``
beside ``n_routed_experts``): the router keeps the published width, the tree
holds ``n_routed_experts`` experts from ``first_routed_expert`` on
(ops/moe.py ``MoEArch.held_experts``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import numpy as np

from nxdi_tpu.config import InferenceConfig
from nxdi_tpu.models import dense
from nxdi_tpu.models.base import DecoderArch, decoder_param_specs
from nxdi_tpu.ops.mla import (
    MLAArch,
    deinterleave_rope_columns,
    mla_param_specs,
    mla_shape_struct,
)
from nxdi_tpu.ops.moe import MoEArch, moe_parallel_fields
from nxdi_tpu.ops.rope import default_inv_freq, yarn_inv_freq


class DeepseekInferenceConfig(dense.DenseInferenceConfig):
    REQUIRED = [
        "hidden_size",
        "num_attention_heads",
        "num_hidden_layers",
        "vocab_size",
        "intermediate_size",
        "rms_norm_eps",
        "kv_lora_rank",
        "qk_rope_head_dim",
        "qk_nope_head_dim",
        "v_head_dim",
    ]

    def add_derived_config(self):
        if not hasattr(self, "num_key_value_heads"):
            self.num_key_value_heads = self.num_attention_heads
        super().add_derived_config()
        for k, v in {
            "q_lora_rank": None,
            "rope_interleave": True,
            "attention_bias": False,
        }.items():
            if not hasattr(self, k):
                setattr(self, k, v)


class PanguUltraMoeInferenceConfig(DeepseekInferenceConfig):
    """openPangu-Ultra-MoE (``model_type: pangu_ultra_moe``): what its
    ``config.json`` leaves to the modeling code. The router scores with a
    sigmoid and selects without a correction bias or groups (renormalised
    top k x ``routed_scaling_factor``); RoPE channels are in rotate-half order
    (no conversion-time permutation); the multi-token-prediction module
    (``num_nextn_predict_layers``) is no part of the served forward pass."""

    def add_derived_config(self):
        for k, v in {
            "scoring_func": "sigmoid",
            "router_correction_bias": False,
            "rope_interleave": False,
            "sandwich_norm": True,
        }.items():
            if not hasattr(self, k):
                setattr(self, k, v)
        super().add_derived_config()


def _yarn_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _mla_arch(config: InferenceConfig) -> MLAArch:
    tp = config.tpu_config.tp_degree
    H = config.num_attention_heads
    if H % tp != 0:
        raise ValueError(
            f"MLA requires num_attention_heads ({H}) divisible by tp ({tp}) "
            "(no GQA replication path; reference asserts the same)"
        )
    qk_head_dim = config.qk_nope_head_dim + config.qk_rope_head_dim
    scale = qk_head_dim ** -0.5
    rs = getattr(config, "rope_scaling", None)
    if rs:
        mscale_all_dim = rs.get("mscale_all_dim", 0)
        if mscale_all_dim:
            m = _yarn_mscale(rs["factor"], mscale_all_dim)
            scale = scale * m * m
    return MLAArch(
        num_heads=H,
        q_lora_rank=getattr(config, "q_lora_rank", None),
        kv_lora_rank=config.kv_lora_rank,
        qk_nope_head_dim=config.qk_nope_head_dim,
        qk_rope_head_dim=config.qk_rope_head_dim,
        v_head_dim=config.v_head_dim,
        softmax_scale=scale,
    )


def _moe_arch(config: InferenceConfig) -> Optional[MoEArch]:
    """V3/V2 MoE description from the HF config (None for all-dense models).

    HF DeepseekV3TopkRouter semantics: sigmoid scores, selection over
    bias-corrected scores with grouped top-k (n_group groups, topk_group
    kept), weights from the UNCORRECTED scores, renormalized and scaled by
    routed_scaling_factor. Shared experts are n_shared_experts plain
    (ungated) MLPs of moe_intermediate_size each, fused here into one wide
    shared MLP."""
    held = getattr(config, "n_routed_experts", None)
    if not held:
        return None
    # a share: ``n_routed_experts`` counts the experts held here, the router
    # keeps the published width
    E = getattr(config, "n_routed_experts_total", None) or held
    scoring = getattr(config, "scoring_func", "sigmoid")
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"deepseek scoring_func {scoring!r} not supported")
    n_shared = getattr(config, "n_shared_experts", None) or 0
    return MoEArch(
        num_experts=E,
        top_k=config.num_experts_per_tok,
        intermediate_size=config.moe_intermediate_size,
        hidden_act=getattr(config, "hidden_act", "silu"),
        norm_topk_prob=bool(getattr(config, "norm_topk_prob", True)),
        sigmoid_routing=scoring == "sigmoid",
        n_group=getattr(config, "n_group", None),
        topk_group=getattr(config, "topk_group", None),
        routed_scaling=float(getattr(config, "routed_scaling_factor", 1.0)),
        correction_bias=(
            scoring == "sigmoid" and bool(getattr(config, "router_correction_bias", True))
        ),
        shared_expert_intermediate_size=(
            n_shared * config.moe_intermediate_size if n_shared else None
        ),
        held_experts=held if held != E else None,
        first_held=int(getattr(config, "first_routed_expert", 0) or 0),
        **moe_parallel_fields(config.tpu_config, E),
    )


def _first_k_dense(config: InferenceConfig) -> int:
    if getattr(config, "n_routed_experts", None):
        return int(getattr(config, "first_k_dense_replace", 0) or 0)
    return 0


def build_arch(config: InferenceConfig, **overrides) -> DecoderArch:
    # the yarn attention factor (rope_mscale) is computed by dense.build_arch;
    # it depends only on the scaling config, not on which head_dim the
    # frequencies use
    moe = _moe_arch(config)
    if moe is not None and _first_k_dense(config) >= config.num_hidden_layers:
        moe = None  # every layer is dense — no MoE layer exists in the model
    kwargs = dict(
        mla=_mla_arch(config), moe=moe,
        sandwich_norm=bool(getattr(config, "sandwich_norm", False)),
    )
    kwargs.update(overrides)
    return dense.build_arch(config, **kwargs)


def build_inv_freq(config: InferenceConfig) -> np.ndarray:
    rs = getattr(config, "rope_scaling", None)
    theta = getattr(config, "rope_theta", 10000.0)
    if rs and rs.get("rope_type", rs.get("type")) == "yarn":
        return yarn_inv_freq(
            config.qk_rope_head_dim, theta, rs,
            getattr(config, "max_position_embeddings", 4096),
        )[0]
    return default_inv_freq(config.qk_rope_head_dim, theta)


def _dense_mlp(state_dict, pre, cast):
    key = pre + "mlp.gate_proj.weight"
    if key not in state_dict and f"model.{key}" not in state_dict:
        raise ValueError(
            f"deepseek layer {pre.rstrip('.')} has no dense mlp weights; "
            "MoE layers require n_routed_experts in the config"
        )

    def get(name):
        for k in (name, f"model.{name}"):
            if k in state_dict:
                return state_dict[k]
        raise KeyError(name)

    return {
        "gate_proj": {"w": cast(get(pre + "mlp.gate_proj.weight")).T},
        "up_proj": {"w": cast(get(pre + "mlp.up_proj.weight")).T},
        "down_proj": {"w": cast(get(pre + "mlp.down_proj.weight")).T},
    }


def _moe_layer(state_dict, pre, cast, moe: MoEArch):
    def get(name):
        for k in (name, f"model.{name}"):
            if k in state_dict:
                return state_dict[k]
        raise KeyError(name)

    held = range(moe.first_held, moe.first_held + moe.experts_here)

    def stacked(proj):
        return {"w": cast(np.stack([
            np.asarray(get(f"{pre}mlp.experts.{j}.{proj}.weight")).T for j in held
        ]))}

    out: Dict[str, Any] = {
        "router": {"w": cast(get(pre + "mlp.gate.weight")).T},
        "experts": {
            "gate_proj": stacked("gate_proj"),
            "up_proj": stacked("up_proj"),
            "down_proj": stacked("down_proj"),
        },
    }
    if moe.correction_bias:
        # selection-only bias kept in f32 like HF (bf16 rounding here flips
        # near-tie expert selections vs the CPU golden)
        out["router"]["e_bias"] = np.asarray(
            get(pre + "mlp.gate.e_score_correction_bias"), np.float32
        )
    if moe.shared_expert_intermediate_size:
        out["shared_expert"] = {
            "gate_proj": {"w": cast(get(pre + "mlp.shared_experts.gate_proj.weight")).T},
            "up_proj": {"w": cast(get(pre + "mlp.shared_experts.up_proj.weight")).T},
            "down_proj": {"w": cast(get(pre + "mlp.shared_experts.down_proj.weight")).T},
        }
    return out


def convert_hf_state_dict(
    state_dict: Dict[str, np.ndarray], config: InferenceConfig
) -> Dict[str, Any]:
    arch = build_arch(config)
    mla: MLAArch = arch.mla
    dt = dense.np_dtype(arch.dtype)
    interleave = bool(getattr(config, "rope_interleave", True))

    def get(name):
        for k in (name, f"model.{name}"):
            if k in state_dict:
                return state_dict[k]
        raise KeyError(name)

    def cast(x):
        return np.asarray(x, dtype=dt)

    layers = []
    for i in range(arch.num_layers):
        pre = f"layers.{i}."
        attn: Dict[str, Any] = {
            "kv_a": {"w": cast(get(pre + "self_attn.kv_a_proj_with_mqa.weight")).T},
            "kv_a_norm": cast(get(pre + "self_attn.kv_a_layernorm.weight")),
            "kv_b": {"w": cast(get(pre + "self_attn.kv_b_proj.weight")).T},
            "o_proj": {"w": cast(get(pre + "self_attn.o_proj.weight")).T},
        }
        if mla.q_lora_rank is None:
            attn["q_proj"] = {"w": cast(get(pre + "self_attn.q_proj.weight")).T}
            q_key = "q_proj"
        else:
            attn["q_a"] = {"w": cast(get(pre + "self_attn.q_a_proj.weight")).T}
            attn["q_a_norm"] = cast(get(pre + "self_attn.q_a_layernorm.weight"))
            attn["q_b"] = {"w": cast(get(pre + "self_attn.q_b_proj.weight")).T}
            q_key = "q_b"
        if interleave:
            # fold the interleaved-rope channel permutation into the weights
            attn[q_key]["w"] = deinterleave_rope_columns(
                attn[q_key]["w"], mla.qk_head_dim, mla.qk_nope_head_dim, mla.qk_rope_head_dim
            )
            kv_a = attn["kv_a"]["w"]
            rope_cols = kv_a[:, mla.kv_lora_rank:]
            perm = np.concatenate(
                [np.arange(0, mla.qk_rope_head_dim, 2), np.arange(1, mla.qk_rope_head_dim, 2)]
            )
            attn["kv_a"]["w"] = np.concatenate(
                [kv_a[:, : mla.kv_lora_rank], rope_cols[:, perm]], axis=1
            )
        layer = {
            "input_layernorm": cast(get(pre + "input_layernorm.weight")),
            "post_attention_layernorm": cast(get(pre + "post_attention_layernorm.weight")),
            "attn": attn,
        }
        if arch.sandwich_norm:
            # the published names, onto the slots models/base.py reads
            layer["pre_feedforward_layernorm"] = cast(get(pre + "pre_mlp_layernorm.weight"))
            layer["post_feedforward_layernorm"] = cast(get(pre + "post_mlp_layernorm.weight"))
        if arch.moe is not None and i >= _first_k_dense(config):
            layer["moe"] = _moe_layer(state_dict, pre, cast, arch.moe)
        else:
            layer["mlp"] = _dense_mlp(state_dict, pre, cast)
        layers.append(layer)

    k_dense = _first_k_dense(config)
    if arch.moe is not None and 0 < k_dense < arch.num_layers:
        stacked = [dense.tree_stack(layers[:k_dense]), dense.tree_stack(layers[k_dense:])]
    else:
        stacked = dense.tree_stack(layers)
    params: Dict[str, Any] = {
        "embed_tokens": cast(get("embed_tokens.weight")),
        "layers": stacked,
        "norm": cast(get("norm.weight")),
    }
    vocab_pad = arch.vocab_pad
    if vocab_pad:
        e = params["embed_tokens"]
        params["embed_tokens"] = np.concatenate(
            [e, np.zeros((vocab_pad, e.shape[1]), dtype=e.dtype)], axis=0
        )
    if not arch.tie_word_embeddings:
        head = (
            state_dict.get("lm_head.weight")
            if "lm_head.weight" in state_dict
            else params["embed_tokens"][: config.vocab_size]
        )
        head = np.asarray(head, dtype=dt)
        if vocab_pad:
            head = np.concatenate(
                [head, np.zeros((vocab_pad, head.shape[1]), dtype=dt)], axis=0
            )
        params["lm_head"] = head.T
    return params


def _segment_archs(config: InferenceConfig, arch: DecoderArch):
    """(dense-head arch, moe-tail arch) for segmented stacks, or None when the
    stack is homogeneous."""
    k = _first_k_dense(config)
    if arch.moe is None or not (0 < k < arch.num_layers):
        return None
    head = dataclasses.replace(arch, num_layers=k, moe=None)
    tail = dataclasses.replace(arch, num_layers=arch.num_layers - k)
    return head, tail


def param_specs(config: InferenceConfig):
    import jax

    from jax.sharding import PartitionSpec as P

    arch = build_arch(config)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: P(*((None,) + tuple(s))), tree, is_leaf=lambda x: isinstance(x, P)
        )

    mla_specs = stack(mla_param_specs(arch.mla))

    def finish(layer_specs):
        layer_specs["attn"] = mla_specs
        if arch.sandwich_norm:
            layer_specs["pre_feedforward_layernorm"] = P()
            layer_specs["post_feedforward_layernorm"] = P()
        return layer_specs

    segs = _segment_archs(config, arch)
    specs = dense.param_specs_for(arch)
    if segs is None:
        finish(specs["layers"])
        return specs
    seg_specs = []
    for seg_arch in segs:
        seg_specs.append(finish(decoder_param_specs(seg_arch)["layers"]))
    specs["layers"] = seg_specs
    return specs


def param_shape_struct(config: InferenceConfig):
    from nxdi_tpu.config import to_jax_dtype

    import jax

    arch = build_arch(config)
    dt = to_jax_dtype(arch.dtype)

    def finish(layers, seg_arch):
        layers["attn"] = mla_shape_struct(
            seg_arch.mla, seg_arch.hidden_size, seg_arch.num_layers, dt
        )
        if arch.sandwich_norm:
            norm = jax.ShapeDtypeStruct((seg_arch.num_layers, seg_arch.hidden_size), dt)
            layers["pre_feedforward_layernorm"] = norm
            layers["post_feedforward_layernorm"] = norm
        return layers

    struct = dense.param_shape_struct(config, arch)
    segs = _segment_archs(config, arch)
    if segs is None:
        finish(struct["layers"], arch)
        return struct
    struct["layers"] = [
        finish(dense.param_shape_struct(config, seg_arch)["layers"], seg_arch)
        for seg_arch in segs
    ]
    return struct
