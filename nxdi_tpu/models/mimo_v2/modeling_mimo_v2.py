"""MiMo-V2-Flash — hybrid full/sliding-window MoE decoder with asymmetric
q/k vs v head widths (the reference's second published-benchmark model).

Reference: models/mimo_v2/modeling_mimo_v2.py (1975 LoC). Architectural
pieces and how they land here:
  - hybrid_layer_pattern: per-layer full vs sliding-window attention with
    INDEPENDENT head counts, head dims, and rope theta per type (:276) —
    expressed as two DecoderArch variants walked in depth-ordered segments,
    each type owning its own layer-stacked KV cache.
  - asymmetric q/k head_dim (192) vs v head_dim (128) (:324) —
    DecoderArch.v_head_dim; the cache stores v at its own width.
  - partial rotary (partial_rotary_factor, even-rounded) per type ->
    DecoderArch.rotary_dim.
  - moe_layer_freq: per-layer MoE or dense MLP (:888) — segments also split
    on the ff-type boundary; sigmoid router, renormalized top-k, chosen over
    ``scores + e_score_correction_bias`` (``topk_method: noaux_tc``).
  - ``add_swa_attention_sink_bias``: the window layers' softmax has one more
    column a head, a learned logit whose probability is dropped
    (``DecoderArch.attention_sink`` on the window arch alone);
    ``attention_value_scale``: ``v = v_proj(x) * scale``, in the graph.
  - the three multi-token-prediction layers are no part of the served pass.

One chip's share of an expert-parallel deployment (``n_routed_experts_total``
in the config): the router keeps its published width, this chip's tree holds
``n_routed_experts`` experts from ``first_routed_expert`` on (ops/moe.py
``MoEArch.held_experts``, as models/deepseek reads the same keys).

The walk over the two kinds of layer, their two kinds of cache (the full
layers' block pool, the window layers' ring rows a slot) and the application
are ``models/window_full.py``'s, shared with K-EXAONE (``models/exaone_moe``).

HF weight layout: llama-style attention (+ ``self_attn.attention_sink_bias``);
router ``mlp.gate`` (+ ``e_score_correction_bias``); experts
``mlp.experts.{i}.gate/up/down_proj``; dense layers ``mlp.gate/up/down_proj``.
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


class MiMoV2InferenceConfig(dense.DenseInferenceConfig):
    REQUIRED = [
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "v_head_dim", "vocab_size",
        "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts",
        "num_experts_per_tok", "moe_intermediate_size", "partial_rotary_factor",
        "sliding_window", "swa_head_dim", "swa_v_head_dim",
        "swa_num_attention_heads", "swa_num_key_value_heads", "swa_rope_theta",
        "rope_theta",
    ]

    def add_derived_config(self):
        # the published lists cover all 48 layers: a cut in depth reads a prefix
        n = self.num_hidden_layers
        for key in ("hybrid_layer_pattern", "moe_layer_freq"):
            if len(getattr(self, key)) < n:
                raise ValueError(f"{key} has {len(getattr(self, key))} entries for {n} layers")
            setattr(self, key, list(getattr(self, key))[:n])
        if not hasattr(self, "rms_norm_eps"):
            self.rms_norm_eps = getattr(self, "layernorm_epsilon", 1e-6)
        if not hasattr(self, "intermediate_size"):
            # dense layers use the plain intermediate size; experts use
            # moe_intermediate_size
            self.intermediate_size = getattr(
                self, "dense_intermediate_size", self.moe_intermediate_size
            )
        super().add_derived_config()


def _rope_dim(head_dim: int, factor: float) -> int:
    rd = int(head_dim * factor)
    return rd - (rd % 2)


def _moe_arch(config: InferenceConfig) -> MoEArch:
    # a share: ``n_routed_experts`` counts the experts held here, the router
    # keeps the published width (models/deepseek reads the same three keys)
    held = config.n_routed_experts
    E = getattr(config, "n_routed_experts_total", None) or held
    sigmoid = str(getattr(config, "scoring_func", "sigmoid")) == "sigmoid"
    n_group = getattr(config, "n_group", None) or 1
    return MoEArch(
        num_experts=E,
        top_k=config.num_experts_per_tok,
        intermediate_size=config.moe_intermediate_size,
        hidden_act=getattr(config, "hidden_act", "silu"),
        norm_topk_prob=bool(getattr(config, "norm_topk_prob", True)),
        sigmoid_routing=sigmoid,
        n_group=n_group if n_group > 1 else None,
        topk_group=getattr(config, "topk_group", None) if n_group > 1 else None,
        routed_scaling=float(getattr(config, "routed_scaling_factor", None) or 1.0),
        # noaux_tc: the top k are chosen over scores + a learned bias, the
        # weights come from the scores alone
        correction_bias=sigmoid and getattr(config, "topk_method", None) == "noaux_tc",
        held_experts=held if held != E else None,
        first_held=int(getattr(config, "first_routed_expert", 0) or 0),
        # the chip a share is built for sizes its slots (ops/moe.py compact_capacity)
        ridge_rows=resolve_chip(config.tpu_config).ridge_rows if held != E else None,
        **moe_parallel_fields(config.tpu_config, E),
    )


def _layer_types(config) -> List[str]:
    return [WINDOW if p == 1 else FULL for p in config.hybrid_layer_pattern]


def _layer_moe(config) -> List[bool]:
    return [bool(f) for f in config.moe_layer_freq]


def build_arch(config: InferenceConfig, **overrides) -> WindowFullArch:
    tp = config.tpu_config.tp_degree
    prf = float(config.partial_rotary_factor)
    types = _layer_types(config)
    moe = _moe_arch(config)

    def type_arch(kind: str) -> DecoderArch:
        if kind == WINDOW:
            heads, kv = config.swa_num_attention_heads, config.swa_num_key_value_heads
            hd, vd = config.swa_head_dim, config.swa_v_head_dim
            window = config.sliding_window
            sink = bool(getattr(config, "add_swa_attention_sink_bias", False))
        else:
            heads, kv = config.num_attention_heads, config.num_key_value_heads
            hd, vd = config.head_dim, config.v_head_dim
            window = None
            sink = bool(getattr(config, "add_full_attention_sink_bias", False))
        plan = gqa.plan_gqa_sharding(tp, heads, kv)
        return dense.build_arch(
            config,
            num_layers=types.count(kind),
            num_attention_heads=plan.target_heads,
            num_kv_heads=plan.target_kv,
            head_dim=hd,
            v_head_dim=None if vd == hd else vd,
            sliding_window=window,
            rotary_dim=(lambda rd: rd if rd < hd else None)(_rope_dim(hd, prf)),
            attention_sink=sink,
            attention_value_scale=getattr(config, "attention_value_scale", None),
            # stated, not left to a kernel's default: the pool pads its key
            # rows to a lane tile, and the scale is the head's, not the row's
            attention_scale=float(hd) ** -0.5,
            moe=moe,
            **overrides,
        )

    # depth walk, splitting segments on (type, ff-kind) boundaries
    return window_full.build(type_arch(FULL), type_arch(WINDOW), types, _layer_moe(config))


def build_inv_freq(config: InferenceConfig) -> Dict[str, np.ndarray]:
    prf = float(config.partial_rotary_factor)
    return {
        FULL: inv_freq_from_hf_config(
            _rope_dim(config.head_dim, prf), config.rope_theta, None
        ),
        WINDOW: inv_freq_from_hf_config(
            _rope_dim(config.swa_head_dim, prf),
            getattr(config, "swa_rope_theta", 10000.0),
            None,
        ),
    }


# ---------------------------------------------------------------------------
# Conversion / specs / structs
# ---------------------------------------------------------------------------


def _convert_layer(state_dict, config, arch: WindowFullArch, i: int, kind: str, use_moe: bool):
    ta = arch.of(kind)
    tp = config.tpu_config.tp_degree
    if kind == WINDOW:
        plan = gqa.plan_gqa_sharding(
            tp, config.swa_num_attention_heads, config.swa_num_key_value_heads
        )
    else:
        plan = gqa.plan_gqa_sharding(
            tp, config.num_attention_heads, config.num_key_value_heads
        )
    D = ta.head_dim
    Dv = ta.v_head_dim or D
    dt = dense.np_dtype(ta.dtype)
    cast = lambda x: np.asarray(x, dt)  # noqa: E731
    pre = f"model.layers.{i}."

    def get(name):
        for k in (pre + name, pre.replace("model.", "", 1) + name):
            if k in state_dict:
                return state_dict[k]
        raise KeyError(pre + name)

    layer = {
        "input_layernorm": cast(get("input_layernorm.weight")),
        "post_attention_layernorm": cast(get("post_attention_layernorm.weight")),
        "attn": {
            "q_proj": {"w": cast(gqa.convert_q(get("self_attn.q_proj.weight"), D, plan).T)},
            "k_proj": {"w": cast(gqa.convert_kv(get("self_attn.k_proj.weight"), D, plan).T)},
            "v_proj": {"w": cast(gqa.convert_kv(get("self_attn.v_proj.weight"), Dv, plan).T)},
            "o_proj": {"w": cast(gqa.convert_o(get("self_attn.o_proj.weight"), Dv, plan).T)},
        },
    }
    if ta.attention_sink:
        # one logit a q head, in the q weights' head order
        sink = np.asarray(get("self_attn.attention_sink_bias"), np.float32)
        layer["attn"]["sink"] = cast(gqa.convert_q(sink[:, None], 1, plan)[:, 0])
    if use_moe:
        moe = ta.moe
        first = moe.first_held  # a share converts its own experts alone
        layer["moe"] = convert_hf_experts(
            get,
            cast,
            moe.experts_here,
            "mlp.gate.weight",
            lambda j, proj: f"mlp.experts.{first + j}.{proj}_proj.weight",
        )
        if moe.correction_bias:  # selection only: float32, as the scores are
            layer["moe"]["router"]["e_bias"] = np.asarray(
                get("mlp.gate.e_score_correction_bias"), np.float32
            )
    else:
        layer["mlp"] = {
            "gate_proj": {"w": cast(get("mlp.gate_proj.weight").T)},
            "up_proj": {"w": cast(get("mlp.up_proj.weight").T)},
            "down_proj": {"w": cast(get("mlp.down_proj.weight").T)},
        }
    return layer


def convert_hf_state_dict(
    state_dict: Dict[str, np.ndarray], config: InferenceConfig
) -> Dict[str, Any]:
    arch = build_arch(config)
    return window_full.convert_segments(
        state_dict, arch,
        lambda i, kind, routed: _convert_layer(state_dict, config, arch, i, kind, routed),
    )


def param_specs(config: InferenceConfig):
    return window_full.param_specs(build_arch(config))


def param_shape_struct(config: InferenceConfig):
    return window_full.param_shape_struct(build_arch(config))


class MiMoV2ForCausalLM:
    def __new__(cls, *args, **kwargs):
        from nxdi_tpu.models.mimo_v2.application import MiMoV2Application

        return MiMoV2Application(*args, **kwargs)


def __getattr__(name):
    # lazy APPLICATION_CLS: application.py imports this module, so a
    # top-level import back would be circular
    if name == "APPLICATION_CLS":
        from nxdi_tpu.models.mimo_v2.application import MiMoV2Application

        return MiMoV2Application
    raise AttributeError(name)
