"""The compiled decoder graph — pure-functional analog of the reference's
``NeuronBaseModel`` (models/model_base.py:99, forward :713).

What the reference expresses as a traced torch module mutating Parameter KV
caches, we express as a pure function over (params, kv_cache, batch) returning
(outputs, new_kv_cache), jitted per (submodel, bucket) with the cache donated.

Structure of one forward (reference: model_base.py:1264 ``get_model_output``):
  embed -> [scan over decoder layers: rmsnorm -> attention(+KV update) ->
  residual -> rmsnorm -> MLP -> residual] -> final rmsnorm -> last-token gather
  -> lm_head -> padded-logit mask -> on-device sampler.

The layer stack runs as ONE ``lax.scan`` over layer-stacked params: a single
compiled layer body regardless of depth, which keeps XLA compile times flat as
models grow. Heterogeneous stacks (e.g. interleaved sliding-window layers) pass
per-layer scalars through the scan xs. Where the KV cache lives in that scan
depends on its layout (kvcache/kv_cache.py): the paged pool is the scan's
CARRY — one donated buffer written at (layer, slot) in place and read by the
paged kernels at (layer, block); the contiguous decode path reads the old
stack and commits fresh rows once after the scan; only what is left (contiguous
prefill, ring, MLA) rides the scan as per-layer xs/ys slices.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax.sharding import PartitionSpec as P

from nxdi_tpu.kvcache.kv_cache import (
    DEFAULT_KV_LAYOUT,
    BlockKVCacheSpec,
    BlockKVLayout,
    ContiguousKVLayout,
    KVCacheSpec,
)
from nxdi_tpu.ops import attention as attn_ops
from nxdi_tpu.ops import attention_select as attn_select
from nxdi_tpu.ops import kernels as attn_kernels
from nxdi_tpu.ops import moe as moe_ops
from nxdi_tpu.ops import quantization as quant_ops
from nxdi_tpu.ops import sampling as sampling_ops
from nxdi_tpu.ops.norms import rms_norm
from nxdi_tpu.ops.rope import apply_rotary_pos_emb, rope_cos_sin
from nxdi_tpu.parallel.layers import (
    COLUMN_PARALLEL,
    REPLICATED,
    ROW_PARALLEL,
    VOCAB_PARALLEL,
    constrain,
)
from nxdi_tpu.parallel.mesh import AXIS_MP, AXIS_PP
from nxdi_tpu.parallel.policy import DEFAULT_POLICY, ShardingPolicy

ACT_FNS: Dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "gelu_pytorch_tanh": partial(jax.nn.gelu, approximate=True),
    "gelu_new": partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
    # squared ReLU (persimmon, arcee/AFM — HF ACT2FN["relu2"])
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def xielu(x: jax.Array, alpha_p: jax.Array, alpha_n: jax.Array) -> jax.Array:
    """xIELU activation (apertus; arxiv 2411.13010). ``alpha_p``/``alpha_n``
    are the POST-softplus per-layer scalars (host-computed at conversion to
    reproduce HF's bfloat16 parameter rounding — XIELUActivation keeps its
    learnables in bf16 regardless of model dtype)."""
    xf = x.astype(jnp.float32)
    beta = jnp.float32(0.5)
    # HF stores eps as a bf16 buffer; bake the same rounding
    eps = jnp.float32(np.float32(np.asarray(-1e-6, dtype=ml_dtypes.bfloat16)))
    pos = alpha_p * xf * xf + beta * xf
    neg = (jnp.expm1(jnp.minimum(xf, eps)) - xf) * alpha_n + beta * xf
    return jnp.where(xf > 0, pos, neg).astype(x.dtype)


@dataclass(frozen=True)
class DecoderArch:
    """Static (hashable) architecture description closed over by the jitted fns.

    Head/vocab counts are the PADDED values after GQA sharding planning
    (parallel/gqa.py) and vocab padding; original sizes are kept for masking.
    """

    num_layers: int
    hidden_size: int
    num_attention_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    vocab_size: int  # padded
    vocab_pad: int  # rows added to reach vocab_size
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    attention_bias: bool = False
    mlp_bias: bool = False
    qk_norm: bool = False  # qwen3-style per-head q/k rmsnorm
    sliding_window: Optional[int] = None
    chunk_size: Optional[int] = None  # llama4 chunked attention
    attention_scale: Optional[float] = None
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    softmax_dtype: str = "float32"
    # Pallas kernel gates (reference: attn_kernel_enabled flags config.py:418-533)
    attn_kernel_enabled: bool = False
    attn_tkg_kernel_enabled: bool = False
    attn_block_tkg_kernel_enabled: bool = False  # paged decode through table
    # fused projections (reference: fused_qkv gqa.py:530-683, qkv/mlp NKI
    # kernels modeling_llama.py:502-943). fused_qkv packs q/k/v into ONE
    # weight with per-tp-rank head-block interleave (dense.fuse_qkv_weights);
    # the kernel flags route the fused matmuls through ops/kernels/fused_proj.
    # All three are enforced loudly: ModelWrapper raises after lowering if an
    # enabled flag's strategy never engaged (no silent no-ops).
    fused_qkv: bool = False
    fused_qkv_tp: int = 1  # tp degree the fused weight was interleaved for
    qkv_kernel_enabled: bool = False
    mlp_kernel_enabled: bool = False
    # pipeline parallel: layer stack sharded over the pp mesh axis, GPipe
    # microbatch rotation in run_decoder_layers (reference: pp_degree,
    # models/config.py:366, application_base.py:158-163)
    pp_degree: int = 1
    pp_microbatches: int = 0  # 0 = pp_degree
    # dynamic activation quantization (reference: ActivationQuantizationType
    # config.py:434-517); weights themselves are quantized in the params pytree
    act_quant: Optional[str] = None
    act_clamp: Optional[float] = None
    # MoE feed-forward replaces the dense MLP when set (ops/moe.py)
    moe: Optional[moe_ops.MoEArch] = None
    # gemma lineage (reference: models/gemma3/modeling_gemma3.py): (1+w)
    # float32 norms, sandwich (pre+post) feed-forward norms, sqrt(H) embedding
    # scale; per-layer sliding-window/rope selection rides the layer scan as
    # params flags ("use_sliding_window", "use_local_rope")
    gemma_norm: bool = False
    sandwich_norm: bool = False
    embed_scale: Optional[float] = None
    # gpt-oss style learned attention-sink logits (params: attn["sink"] (H,))
    attention_sink: bool = False
    # mimo-v2: v = v_proj(x) * attention_value_scale, in the graph (a converter
    # could fold it into v_proj, but weights that never pass one must not need it)
    attention_value_scale: Optional[float] = None
    # gemma3-vision: prefill image-token spans attend each other
    # bidirectionally (HF token_type_ids_mask_function); needs image_token_id
    bidirectional_image_attention: bool = False
    # dbrx: weight-only LayerNorm instead of RMSNorm; qkv clamp
    layernorm: bool = False
    clip_qkv: Optional[float] = None
    # gpt2 lineage: learned position embeddings added to the token embeds
    # (params["position_embeddings"]), no rope, plain (non-gated) MLP
    learned_pos_embeds: bool = False
    no_rope: bool = False
    gated_mlp: bool = True
    # o_proj bias (gpt-oss; the llama lineage never has one)
    attention_o_bias: bool = False
    # Trinity/Afmoe gated attention: ctx *= sigmoid(gate_proj(attn input))
    # before o_proj (params: attn["gate_proj"]["w"], q-interleave sharded)
    attn_out_gate: bool = False
    # YaRN attention factor multiplying cos/sin (gpt-oss, deepseek)
    rope_mscale: float = 1.0
    # LongRoPE (phi3 128k): inv_freq arrives stacked (2, D/2) [short, long];
    # the long set activates in-graph when max(position)+1 exceeds this
    # (HF _longrope_frequency_update semantics)
    longrope_original_max: Optional[int] = None
    # Qwen2-VL M-RoPE: head_dim/2 frequency channels partitioned into
    # [temporal, height, width] sections; batch supplies (B, 3, S) position
    # streams as "mrope_position_ids" (reference: models/qwen2_vl/ M-RoPE)
    mrope_section: Optional[Tuple[int, ...]] = None
    mrope_interleaved: bool = False  # qwen3-vl channel-interleaved layout
    # partial rotary (minimax-m2 rotary_dim=64 of head_dim=128; phi lineage):
    # only the first rotary_dim channels rotate, the rest pass through
    rotary_dim: Optional[int] = None
    # minimax-m2 "per_layer" qk norm: RMSNorm over the FLAT projection output
    # (num_heads*head_dim) BEFORE head reshape/rope. Under GQA zero-padding
    # the q denominator must stay the TRUE (unpadded) width — padded entries
    # are exactly zero, so sum(x^2)/true_dim reproduces the unpadded mean;
    # replicated k heads preserve the mean, so k uses the plain mean.
    qk_norm_flat: bool = False
    qk_norm_flat_qdim: int = 0  # true (unpadded) q width
    # asymmetric value width (mimo-v2: q/k head_dim 192, v head_dim 128);
    # None = same as head_dim. Cache stores v at this width.
    v_head_dim: Optional[int] = None
    # Multi-head Latent Attention replaces the GQA attention when set
    # (ops/mla.py; deepseek lineage)
    mla: Optional[Any] = None
    # llama4 (reference: models/llama4/): adjacent-pair (GPT-J) rope layout,
    # unweighted L2 qk-norm AFTER rope, per-position query temperature tuning
    # on no-rope layers; per-layer rope/chunk gating rides the scan via the
    # "use_rope" params flag
    rope_interleaved: bool = False
    qk_l2norm: bool = False
    # gemma2 softcapping: cap*tanh(x/cap) on attention scores / final logits
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    attn_temperature_tuning: bool = False
    floor_scale: float = 8192.0
    attn_scale: float = 0.1
    # olmo2: NO input norms; RMSNorm applied to the attn/mlp OUTPUT before the
    # residual add. Params reuse the standard layer keys: "input_layernorm"
    # holds the post-ATTENTION norm, "post_attention_layernorm" the
    # post-FEEDFORWARD norm (conversion aliases them; HF Olmo2DecoderLayer).
    post_block_norm: bool = False
    # parallel residual (cohere/command-r, gpt-neox use_parallel_residual):
    # x + attn(norm1(x)) + mlp(norm2(x)) in ONE residual add; cohere aliases
    # norm2 to norm1 (same weights), gpt-neox keeps them distinct
    parallel_block: bool = False
    # granite: scalar multipliers on block outputs and logits
    # (HF GraniteForCausalLM residual_multiplier / logits_scaling)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # interleaved sliding-window stacks (gpt-oss alternating, gemma3 5-of-6):
    # per-layer True = sliding-window layer. With window_sized_kv the cache
    # splits into a full-length stack for False layers and a W-slot ring
    # stack for True layers (reference: per-layer window-sized cache shapes,
    # gpt_oss_kv_cache_manager.py, kv_cache_manager.py:195-210); the layer
    # scan runs over the pattern's repeating unit (run_decoder_layers).
    kv_window_pattern: Optional[Tuple[bool, ...]] = None

    @property
    def kv_pattern_period(self) -> int:
        """Smallest repeating unit of kv_window_pattern (the unit-scan body
        compiles one decoder block per unit position)."""
        pat = self.kv_window_pattern
        assert pat is not None
        L = len(pat)
        for p in range(1, L + 1):
            if L % p == 0 and all(pat[i] == pat[i % p] for i in range(L)):
                return p
        return L

    def kv_cache_spec(self, batch_size: int, max_len: int, quant_dtype=None) -> KVCacheSpec:
        if self.mla is not None:
            # latent cache: k holds the shared rotated rope key, v the normed
            # compressed kv latent (ops/mla.py)
            return KVCacheSpec(
                num_layers=self.num_layers,
                batch_size=batch_size,
                num_kv_heads=1,
                max_len=max_len,
                head_dim=self.mla.qk_rope_head_dim,
                v_head_dim=self.mla.kv_lora_rank,
                dtype=self.dtype,
                quant_dtype=quant_dtype,
            )
        return KVCacheSpec(
            num_layers=self.num_layers,
            batch_size=batch_size,
            num_kv_heads=self.num_kv_heads,
            max_len=max_len,
            head_dim=self.head_dim,
            dtype=self.dtype,
            quant_dtype=quant_dtype,
            v_head_dim=self.v_head_dim,
        )


# ---------------------------------------------------------------------------
# Parameter pytree layout + sharding specs
# ---------------------------------------------------------------------------

def attention_param_specs(arch: DecoderArch) -> Dict[str, Any]:
    if arch.fused_qkv:
        # one interleaved weight: column-sharding hands each rank exactly its
        # [q-heads | k-heads | v-heads] block (dense.fuse_qkv_weights)
        spec = {
            "qkv_proj": {"w": COLUMN_PARALLEL},
            "o_proj": {"w": ROW_PARALLEL},
        }
        if arch.attention_bias:
            spec["qkv_proj"]["b"] = P(AXIS_MP)
        if arch.attention_o_bias:
            spec["o_proj"]["b"] = REPLICATED
        if arch.qk_norm:
            spec["q_norm"] = REPLICATED
            spec["k_norm"] = REPLICATED
        return spec
    spec: Dict[str, Any] = {
        "q_proj": {"w": COLUMN_PARALLEL},
        "k_proj": {"w": COLUMN_PARALLEL},
        "v_proj": {"w": COLUMN_PARALLEL},
        "o_proj": {"w": ROW_PARALLEL},
    }
    if arch.attention_bias:
        # Qwen2-style layout: q/k/v carry biases, o_proj does not
        for name in ("q_proj", "k_proj", "v_proj"):
            spec[name]["b"] = P(AXIS_MP)
    if arch.attention_o_bias:  # gpt-oss
        spec["o_proj"]["b"] = REPLICATED
    if arch.qk_norm:
        spec["q_norm"] = REPLICATED
        spec["k_norm"] = REPLICATED
    if arch.attn_out_gate:  # Trinity/Afmoe
        spec["gate_proj"] = {"w": COLUMN_PARALLEL}
    return spec


def mlp_param_specs(arch: DecoderArch) -> Dict[str, Any]:
    spec: Dict[str, Any] = {
        "up_proj": {"w": COLUMN_PARALLEL},
        "down_proj": {"w": ROW_PARALLEL},
    }
    if arch.gated_mlp:
        spec["gate_proj"] = {"w": COLUMN_PARALLEL}
    if arch.mlp_bias:
        if arch.gated_mlp:
            spec["gate_proj"]["b"] = P(AXIS_MP)
        spec["up_proj"]["b"] = P(AXIS_MP)
        spec["down_proj"]["b"] = REPLICATED
    return spec


def decoder_param_specs(arch: DecoderArch) -> Dict[str, Any]:
    """PartitionSpec pytree matching the params pytree produced by the model's
    checkpoint converter. Layer-stacked leaves get their layer dim unsharded
    (P(None, ...) prefix is implicit: specs rank-match via GSPMD trailing rules,
    so we write them explicitly below)."""

    # layer-stacked leaves: the leading (layer) axis shards over pp when
    # pipeline parallel is on — each stage holds its contiguous layer slice
    layer_axis = AXIS_PP if arch.pp_degree > 1 else None

    def stack(spec_tree):
        # prepend the layer axis to every leaf spec
        return jax.tree_util.tree_map(
            lambda s: P(*((layer_axis,) + tuple(s))),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    layer_specs = {
        "input_layernorm": REPLICATED,
        "post_attention_layernorm": REPLICATED,
        "attn": attention_param_specs(arch),
    }
    if arch.moe is not None:
        layer_specs["moe"] = moe_ops.expert_parallel_specs(arch.moe)
    else:
        layer_specs["mlp"] = mlp_param_specs(arch)
    specs = {
        "embed_tokens": VOCAB_PARALLEL,
        "layers": stack(layer_specs),
        "norm": REPLICATED,
    }
    if not arch.tie_word_embeddings:
        specs["lm_head"] = COLUMN_PARALLEL
    return specs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _norm(arch, x, w):
    if isinstance(w, dict):  # biased LayerNorm (gpt2 lineage): {"w", "b"}
        from nxdi_tpu.ops.norms import layer_norm

        return layer_norm(x, w["w"], w.get("b"), eps=arch.rms_norm_eps)
    if arch.layernorm:
        from nxdi_tpu.ops.norms import layer_norm

        return layer_norm(x, w, eps=arch.rms_norm_eps)
    return rms_norm(x, w, arch.rms_norm_eps, gemma_style=arch.gemma_norm)


def _linear(x, p, act_quant=None, clamp=None, adapter_ids=None):
    """Linear over either a full-precision param dict ``{"w"[, "b"]}`` or a
    quantized one ``{"qw", "scale"[, "b"]}`` (ops/quantization.py). When the
    dict carries slot-stacked LoRA buffers (lora/serving.py) and the batch
    supplies ``adapter_ids``, each row adds its adapter's low-rank delta —
    the reference's multi-LoRA linear (lora_serving/lora_layer.py)."""
    if "qw" in p or "qw4" in p:
        y = quant_ops.quantized_linear(x, p, act_quant=act_quant, clamp_bound=clamp)
    else:
        y = x @ p["w"]
        if "b" in p:
            y = y + p["b"]
    if adapter_ids is not None and "lora_A" in p:
        A = p["lora_A"][adapter_ids].astype(x.dtype)  # (B, in, r)
        Bw = p["lora_B"][adapter_ids].astype(x.dtype)  # (B, r, out)
        s = p["lora_scale"][adapter_ids]  # (B,)
        delta = jnp.einsum("b...r,bro->b...o", jnp.einsum("b...i,bir->b...r", x, A), Bw)
        y = y + delta * s[(...,) + (None,) * (y.ndim - 1)].astype(y.dtype)
    return y


def attention_block(
    arch: DecoderArch,
    p_attn: Dict[str, Any],
    hidden: jax.Array,  # (B, S, hidden)
    cos: jax.Array,
    sin: jax.Array,
    k_cache_l: jax.Array,  # contiguous: (B, KV, W, D) view; block: the WHOLE
    v_cache_l: jax.Array,  # (L, slots, KV, D) pool, addressed at ``layer_idx``
    position_ids: jax.Array,  # (B, S)
    cache_spec,  # KVCacheSpec | BlockKVCacheSpec
    attend_to_cache: bool,
    policy: ShardingPolicy = DEFAULT_POLICY,
    layout=DEFAULT_KV_LAYOUT,
    cache_inputs: Optional[Dict[str, jax.Array]] = None,
    adapter_ids: Optional[jax.Array] = None,
    window_enabled: Optional[jax.Array] = None,
    use_rope: Optional[jax.Array] = None,
    defer_write: bool = False,
    qkv_stacked=None,  # (w_s (L,H,T), b_s|None) + stacked_layer_idx: in-scan kernel
    layer_idx=None,  # GLOBAL layer index (KV-quant scale rows; the paged pool's layer)
    stacked_layer_idx=None,  # segment-local index into the stacked weights
    tkg_stacked=None,  # (k_s, v_s, kv_len): stacked-cache fused decode kernel
    spec_window=None,  # (k_sp, v_sp, win_pos, slot): draft-window scratch
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """QKV -> RoPE -> KV update -> attention -> O (reference:
    attention_base.py:571 prep_qkv_tensors, :2075 attention_context_encode).

    ``spec_window`` (fused-speculation draft loop, speculation/fused.py):
    fresh K/V land in a small per-layer (B, KV, spec_len+1, D) scratch at
    column ``slot`` instead of the full cache; attention reads the OLD cache
    with ALL window positions masked (prior windows' stale rows live there)
    plus the scratch as the fresh segment — its per-row rope positions are
    ``win_pos`` and position causality hides the not-yet-written columns.
    Returns the updated scratch slices; the window commits to the full cache
    ONCE after the draft scan, not once per draft step.

    ``defer_write`` (decode hot path): instead of scattering fresh K/V into
    the cache slice and carrying the full slice through the layer scan (XLA
    round-trips the whole cache per layer), attend over the OLD cache with
    this step's slots masked out plus the fresh rows appended, and return
    only the fresh rows — run_decoder_layers commits them all in ONE scatter
    on the stacked cache after the scan. Bitwise-equivalent attention inputs
    (quantized caches round-trip the fresh rows through the store
    dtype/scale first, matching the non-deferred read-after-write); only the
    softmax summation order differs.

    ``attend_to_cache=False`` (context encoding): queries attend the fresh K/V
    only — O(S^2) not O(S * max_len). ``True`` (decode/speculation): attend the
    cache through the layout's read after the in-place update. ``layout``
    (kvcache/kv_cache.py) decides how K/V land: contiguous lines by
    (seq_id, position) or a paged block pool by slot mapping.
    """
    B, S, _ = hidden.shape
    H, KV, D = arch.num_attention_heads, arch.num_kv_heads, arch.head_dim
    Dv = arch.v_head_dim or D  # mimo-v2: value width differs from q/k

    aq, ac = arch.act_quant, arch.act_clamp

    def _o_proj(ctx2d):
        """Output projection, optionally gated (Trinity/Afmoe: the context is
        multiplied by sigmoid(gate_proj(attention input)) before o_proj —
        composes with every attention strategy since the gate acts on the
        kernel-agnostic context)."""
        with jax.named_scope("attn.out"):
            if arch.attn_out_gate:
                g = jax.nn.sigmoid(
                    (hidden @ p_attn["gate_proj"]["w"]).astype(jnp.float32)
                )
                ctx2d = (ctx2d.astype(jnp.float32) * g).astype(ctx2d.dtype)
            return _linear(ctx2d, p_attn["o_proj"], aq, ac, adapter_ids)
    with jax.named_scope("attn.qkv"):
        if arch.fused_qkv:
            if "qkv_proj" not in p_attn:
                raise NotImplementedError(
                    "fused_qkv is enabled but this model's params carry no fused "
                    "qkv_proj weight — the family's converter does not support "
                    "fused QKV; disable the flag"
                )
            pq = p_attn["qkv_proj"]
            Tq, Tk, Tv = H * D, KV * D, KV * Dv
            if arch.qkv_kernel_enabled:
                if adapter_ids is not None or ("w" not in pq and qkv_stacked is None):
                    raise NotImplementedError(
                        "qkv_kernel_enabled requires an unquantized, non-LoRA "
                        "fused qkv_proj weight"
                    )
                if qkv_stacked is not None:
                    w_s, b_s = qkv_stacked
                    qkv = attn_kernels.sharded_qkv_stacked_call(
                        hidden, w_s,
                        layer_idx if stacked_layer_idx is None else stacked_layer_idx,
                        b_s,
                    )
                else:
                    qkv = attn_kernels.sharded_qkv_call(hidden, pq["w"], pq.get("b"))
                if qkv is None:
                    raise NotImplementedError(
                        "qkv_kernel_enabled: fused projection shape is not "
                        "kernel-eligible; disable the flag"
                    )
                attn_select._record_strategy("qkv_fused_kernel")
            else:
                qkv = _linear(hidden, pq, aq, ac, adapter_ids)
                attn_select._record_strategy("qkv_fused_matmul")
            # undo the per-rank interleave on the LOGICAL view: rank blocks are
            # head blocks in order, so regrouping by rank reassembles q/k/v
            tp = arch.fused_qkv_tp
            t = qkv.reshape(B, S, tp, (Tq + Tk + Tv) // tp)
            q = t[..., : Tq // tp].reshape(B, S, Tq)
            k = t[..., Tq // tp : (Tq + Tk) // tp].reshape(B, S, Tk)
            v = t[..., (Tq + Tk) // tp :].reshape(B, S, Tv)
        else:
            q = _linear(hidden, p_attn["q_proj"], aq, ac, adapter_ids)
            k = _linear(hidden, p_attn["k_proj"], aq, ac, adapter_ids)
            v = _linear(hidden, p_attn["v_proj"], aq, ac, adapter_ids)
        if arch.clip_qkv is not None:  # dbrx clamps the qkv outputs
            q = jnp.clip(q, -arch.clip_qkv, arch.clip_qkv)
            k = jnp.clip(k, -arch.clip_qkv, arch.clip_qkv)
            v = jnp.clip(v, -arch.clip_qkv, arch.clip_qkv)
        if arch.qk_norm_flat:
            # minimax-m2: rmsnorm over the whole flattened projection, pre-reshape
            def flat_rms(x, w, denom):
                xf = x.astype(jnp.float32)
                ms = jnp.sum(xf * xf, axis=-1, keepdims=True) / denom
                return (xf * jax.lax.rsqrt(ms + arch.rms_norm_eps) * w).astype(x.dtype)

            q = flat_rms(q, p_attn["q_norm"], arch.qk_norm_flat_qdim or q.shape[-1])
            k = flat_rms(k, p_attn["k_norm"], k.shape[-1])
        q = q.reshape(B, S, H, D)
        k = k.reshape(B, S, KV, D)
        v = v.reshape(B, S, KV, Dv)
        if arch.attention_value_scale is not None:
            # mimo-v2: the values are scaled before they are cached or attended
            v = (v.astype(jnp.float32) * arch.attention_value_scale).astype(v.dtype)

        if arch.qk_norm:
            q = _norm(arch, q, p_attn["q_norm"])
            k = _norm(arch, k, p_attn["k_norm"])

        q = jnp.swapaxes(q, 1, 2)  # (B, H, S, D)
        k = jnp.swapaxes(k, 1, 2)  # (B, KV, S, D)
        v = jnp.swapaxes(v, 1, 2)

        q = constrain(q, policy.q)
        k = constrain(k, policy.kv)
        v = constrain(v, policy.kv)

    with jax.named_scope("attn.rope"):
        rope_fn = apply_rotary_pos_emb
        if arch.rope_interleaved:
            from nxdi_tpu.ops.rope import apply_rotary_pos_emb_interleaved as rope_fn
        if arch.rotary_dim is not None and arch.rotary_dim < D:
            # partial rotary: rotate the first rotary_dim channels only
            # (cos/sin are built from a rotary_dim-sized frequency table)
            rd, base_rope = arch.rotary_dim, rope_fn

            def rope_fn(q_, k_, cos_, sin_):
                qr, kr = base_rope(q_[..., :rd], k_[..., :rd], cos_, sin_)
                return (
                    jnp.concatenate([qr, q_[..., rd:]], axis=-1),
                    jnp.concatenate([kr, k_[..., rd:]], axis=-1),
                )
        if arch.no_rope:
            pass  # gpt2 lineage: positions come from learned embeddings
        elif use_rope is None:
            q, k = rope_fn(q, k, cos, sin)
        else:
            # llama4: some layers skip rope entirely (per-layer scan flag)
            qr, kr = rope_fn(q, k, cos, sin)
            q = jnp.where(use_rope, qr, q)
            k = jnp.where(use_rope, kr, k)

        if arch.qk_l2norm:
            # llama4 unweighted qk norm, AFTER rope, on rope layers only
            from nxdi_tpu.ops.rope import l2_norm

            qn, kn = l2_norm(q, arch.rms_norm_eps), l2_norm(k, arch.rms_norm_eps)
            if use_rope is None:
                q, k = qn, kn
            else:
                q = jnp.where(use_rope, qn, q)
                k = jnp.where(use_rope, kn, k)

        if arch.attn_temperature_tuning and use_rope is not None:
            # per-position query temperature on NO-rope layers
            # (reference: llama4 attn temperature tuning)
            pos = position_ids.astype(jnp.float32)
            scales = (
                jnp.log1p(jnp.floor((pos + 1.0) / arch.floor_scale)) * arch.attn_scale + 1.0
            )[:, None, :, None]
            q = jnp.where(use_rope, q, (q * scales).astype(q.dtype))

    k_store = k
    if isinstance(layout, BlockKVLayout) and arch.mla is None:
        stored = layout.key_tiles(k_cache_l, v_cache_l) * k_cache_l.shape[-1]
        if stored > D:
            # the pool keeps a key row zero-padded to whole lane tiles
            # (kvcache BlockKVLayout, KEY TILES: 192 -> 2 x 128): the fresh
            # keys are padded on their way in, and the queries that attend
            # the pool alike, so the scores are the D-wide ones (the
            # architecture states its scale: D ** -0.5, not the padded width's)
            lanes = ((0, 0), (0, 0), (0, 0), (0, stored - D))
            k_store = jnp.pad(k, lanes)
            if attend_to_cache:
                q, k = jnp.pad(q, lanes), k_store

    ci = dict(cache_inputs or {})
    ci["position_ids"] = position_ids
    if layer_idx is not None:
        # in-scan layer index (the scan's arange xs): per-layer KV-quant
        # scale selection (kv_cache.py _scale_for) and stacked kernels
        ci["layer_idx"] = layer_idx
    if not attend_to_cache and S > 1 and ci.get("write_positions") is None:
        # context encoding from a fresh cache: positions are the row arange
        # starting at 0, so the contiguous layout may take its slice-write
        # fast path instead of a B*S-row scatter (kv_cache.py update)
        ci["prefill_from_zero"] = True
    # which attention computes this call: ops/attention_select.py. It raises
    # where no strategy computes a term the call needs (a bidirectional image
    # span under prefix-cached prefill, a window under mixed dispatch)
    site = attn_select.site_of(
        arch, layout, policy, ci, q.shape, k.shape, k_cache_l, cache_spec.compute_dtype,
        attend_to_cache=attend_to_cache, deferred=defer_write and attend_to_cache,
        layer_flags=(window_enabled is not None, use_rope is not None),
        stacked=tkg_stacked is not None and stacked_layer_idx is not None,
        spec_window=spec_window is not None,
        v_cache=v_cache_l if isinstance(layout, BlockKVLayout) else None,
    )
    name = attn_select.select(site)

    # -- the write
    if site.phase == "spec_window":
        # rows written by earlier draft steps are visible at their true
        # positions, unwritten columns sit at future positions the causal mask
        # hides: the same (position, value) set as the per-step commit path
        k_sp, v_sp, win_pos, slot = spec_window
        with jax.named_scope("kv.write"):
            k_sp = jax.lax.dynamic_update_slice(
                k_sp, k.astype(k_sp.dtype), (0, 0, slot, 0)
            )
            v_sp = jax.lax.dynamic_update_slice(
                v_sp, v.astype(v_sp.dtype), (0, 0, slot, 0)
            )
        k_read, v_read, written = k_cache_l, v_cache_l, (k_sp, v_sp)
    elif site.deferred:  # the fresh rows alone go back, committed after the scan
        k_read, v_read, written = k_cache_l, v_cache_l, (k, v)
    else:
        with jax.named_scope("kv.write"):
            written = layout.update(k_cache_l, v_cache_l, k_store, v, ci, cache_spec)
        k_read, v_read = written

    # -- one core per strategy name, each (B, H, S, Dv)
    sink = None
    if arch.attention_sink:
        with jax.named_scope("attn.sink"):
            sink = p_attn["sink"].astype(jnp.float32)
    # the kernels take the static window and chunk; ops/attention.py every term
    static_terms = dict(
        scale=arch.attention_scale,
        sliding_window=arch.sliding_window,
        chunk_size=arch.chunk_size,
    )
    all_terms = dict(
        static_terms, softmax_dtype=jnp.float32, sink=sink,
        sliding_window_enabled=window_enabled, chunk_enabled=use_rope,
        logit_softcap=arch.attn_logit_softcap,
    )
    if site.phase == "mixed":
        # mixed ragged dispatch (serving one-dispatch step): the packed token
        # stream carries per-token (row, position) tags and one combined
        # per-row block table, so prefill chunks and decode rows share this
        # single attention call
        rids = ci["mixed_row_ids"].astype(jnp.int32)  # (1, S); -1 = padding
        R = ci["last_token_index"].shape[0]  # rows per step (static)

    def read():
        kk, vv, kv_pos = layout.read(k_read, v_read, ci, cache_spec)
        return constrain(kk, policy.cache_kv), constrain(vv, policy.cache_kv), kv_pos

    def attended():
        """What a flat core attends: the fresh rows alone (context encoding:
        O(S^2), not O(S * max_len)) or the written cache's window."""
        return read() if attend_to_cache else (k, v, position_ids)

    def fresh_as_stored():
        store = cache_spec.store_dtype
        array_scales = getattr(layout, "has_array_scales", lambda: False)()
        if store == k.dtype and getattr(layout, "k_scale", 1.0) == 1.0 and not array_scales:
            return k, v
        # quantized cache: round-trip the fresh rows through the store
        # dtype/scale so this step's numerics match the non-deferred
        # path (which attends the quantize->dequantize'd row) exactly
        if array_scales:
            ks = layout._scale_for("k", ci, stacked=False)
            vs = layout._scale_for("v", ci, stacked=False)
        else:
            ks = getattr(layout, "k_scale", 1.0)
            vs = getattr(layout, "v_scale", 1.0)
        clip = ContiguousKVLayout.clip_to_store
        k_att = (clip(k / ks, store).astype(store).astype(k.dtype) * ks).astype(k.dtype)
        v_att = (clip(v / vs, store).astype(store).astype(v.dtype) * vs).astype(v.dtype)
        return k_att, v_att

    def spec_window_xla():
        kk, vv, kv_pos = read()
        kv_pos = jnp.where(kv_pos >= win_pos[:, :1], jnp.int32(2 ** 30), kv_pos)
        return attn_ops.attention_two_part(
            q, kk, vv, k_sp, v_sp, position_ids, kv_pos, win_pos, **all_terms
        )

    def fused_kernel_stacked():  # why: run_decoder_layers, use_stacked_tkg
        k_s, v_s, kv_len_s = tkg_stacked
        return attn_kernels.sharded_fused_decode_stacked_call(
            policy, q, k_s, v_s, k, v, position_ids, stacked_layer_idx,
            kv_len=kv_len_s, **static_terms,
        )

    def fused_kernel():
        # strict-causal online softmax over the old cache merged with the
        # fresh row in ONE pallas pass — the kernel that COMPOSES with
        # deferred writes (reference: fused TKG kernels,
        # attention_base.py:1419-1994); two_part attention is the XLA form
        kk, vv, kv_pos = read()
        k_att, v_att = fresh_as_stored()
        return attn_kernels.sharded_fused_decode_call(
            policy, q, kk, vv, k_att, v_att, position_ids, kv_pos, **static_terms
        )

    def two_part_xla():
        kk, vv, kv_pos = read()
        k_att, v_att = fresh_as_stored()
        wpos = ci.get("write_positions", position_ids).astype(jnp.int32)
        hit = jnp.any(kv_pos[:, None, :] == wpos[:, :, None], axis=1)
        kv_pos = jnp.where(hit, jnp.int32(2 ** 30), kv_pos)
        return attn_ops.attention_two_part(
            q, kk, vv, k_att, v_att, position_ids, kv_pos, wpos, **all_terms
        )

    def paged(call, table, *tags):
        # the rows are in the pool (the write above): the kernels read them
        # through the block table in token order, without BlockKVLayout.read's
        # gather (reference: NKI block kernels, attention_base.py:50-162, 909)
        return call(
            policy, q, k_read, v_read, table, *tags, layer_idx,
            block_size=layout.block_size, scale=arch.attention_scale,
            k_scale=layout.k_scale, v_scale=layout.v_scale,
        )

    def ragged_xla():
        # gather the combined window and rebuild the ragged causal mask from
        # the token tags — kv col g serves row g // row_width at in-row
        # position g % row_width; holes carry the layout's poisoned 2**30
        kk, vv, kv_pos = read()
        W = kk.shape[2]
        row_width = W // R
        g = jnp.arange(W, dtype=jnp.int32)
        mask = (
            (rids[:, :, None] == (g // row_width)[None, None, :])
            & ((g % row_width)[None, None, :] <= position_ids[:, :, None])
            & (kv_pos[0] < jnp.int32(2 ** 30))[None, None, :]
        )
        return attn_ops.grouped_attention(
            q, kk, vv, mask, scale=arch.attention_scale, softmax_dtype=jnp.float32
        )

    def mask_override_xla():
        # explicit (B, S, W) mask — tree-attention verify passes
        # (speculation/token_tree.py) where causal-by-position is wrong
        kk, vv, _ = read()
        return attn_ops.grouped_attention(
            q, kk, vv, ci["attn_mask"][:, :, : kk.shape[2]],
            scale=arch.attention_scale, softmax_dtype=jnp.float32,
            sink=sink, logit_softcap=arch.attn_logit_softcap,
        )

    def flat_kernel():
        kk, vv, kv_pos = attended()
        return attn_kernels.sharded_kernel_call(
            policy, q, kk, vv, position_ids, kv_pos, decode=attend_to_cache, **static_terms,
            # the table: the prefill kernel's terms (a learned sink, a block selection)
            sink=None if attend_to_cache else sink,
            block_mask=None if attend_to_cache else ci.get("block_select"),
        )

    def positions_xla():
        extra_or = None
        if "bidir" in site.needs:
            # gemma3-vision: image-span tokens attend each other BIDIRECTIONALLY
            # during prefill (HF token_type_ids_mask_function OR-ed into both the
            # full and sliding masks); spans are derived in-graph from input_ids
            # (causal_lm_forward), so only the CTE program pays for it
            bidir = ci["bidir_spans"]
            extra_or = (bidir[:, None, :] == bidir[:, :, None]) & (bidir[:, :, None] > 0)
        kk, vv, kv_pos = attended()
        return attn_ops.attention_with_positions(
            q, kk, vv, position_ids, kv_pos, extra_or_mask=extra_or, **all_terms
        )

    cores = {
        "tkg_spec_window_xla": spec_window_xla,
        "tkg_fused_kernel_stacked": fused_kernel_stacked,
        "tkg_fused_kernel": fused_kernel,
        "tkg_two_part_xla": two_part_xla,
        "mixed_ragged_kernel": lambda: paged(
            attn_kernels.sharded_ragged_paged_call,
            ci["block_table"].reshape(R, -1), rids[0], position_ids[0],
        ),
        "mixed_ragged_xla": ragged_xla,
        "cte_paged_kernel": lambda: paged(
            attn_kernels.sharded_paged_prefill_call, ci["block_table"], position_ids
        ),
        "tkg_paged_kernel": lambda: paged(
            attn_kernels.sharded_paged_decode_call, ci["block_table"], position_ids
        ),
        "attn_mask_override_xla": mask_override_xla,
        "tkg_kernel": flat_kernel,
        "tkg_xla": positions_xla,
        "cte_flash_kernel": flat_kernel,
        "cte_xla": positions_xla,
    }
    with jax.named_scope("attn.core"):
        ctx = cores[name]()
    ctx = jnp.swapaxes(ctx, 1, 2).reshape(B, S, H * Dv)
    return _o_proj(ctx), written


def mlp_block(
    arch: DecoderArch, p_mlp: Dict[str, Any], x: jax.Array, adapter_ids=None,
    mlp_stacked=None, layer_idx=None, policy: ShardingPolicy = DEFAULT_POLICY,
) -> jax.Array:
    """Gated MLP (SwiGLU family) — or the plain 2-layer MLP for the gpt2
    lineage (gated_mlp=False). XLA fuses act+mul into the matmuls.

    ``mlp_kernel_enabled`` routes the gated path through the Pallas fused
    gate/up/down kernel (ops/kernels/fused_proj.py; reference: the NKI MLP
    kernel, modeling_llama.py:502-943) — ineligible configurations raise,
    they never silently fall back. Inside the layer scan the weights come
    STACKED (``mlp_stacked`` = (L,H,I)/(L,I,H) arrays + in-scan layer index):
    the kernel indexes them via scalar prefetch, avoiding the per-layer
    slice-copy a pallas operand on scan xs would materialize.

    ``policy.mlp_hidden`` (MLP-CP, reference: mlp_cp_degree
    config.py:364,374-375): when set, the input stream is constrained
    S-sharded on entry and the output re-replicates at the residual join —
    GSPMD inserts the scatter/gather pair the reference wires by hand."""
    with jax.named_scope("mlp"):
        if policy.mlp_hidden is not None and x.shape[1] > 1:
            x = constrain(x, policy.mlp_hidden)
        if arch.mlp_kernel_enabled:
            bad = None
            if not arch.gated_mlp:
                bad = "non-gated MLP"
            elif arch.mlp_bias:
                bad = "MLP biases"
            elif adapter_ids is not None:
                bad = "LoRA adapters"
            elif mlp_stacked is None and any(
                "w" not in p_mlp[k] for k in ("gate_proj", "up_proj", "down_proj")
            ):
                bad = "quantized weights"
            if bad is not None:
                raise NotImplementedError(
                    f"mlp_kernel_enabled does not support {bad}; disable the flag"
                )
            if mlp_stacked is not None:
                gs, us, ds = mlp_stacked
                out = attn_kernels.sharded_fused_mlp_stacked_call(
                    x, gs, us, ds, layer_idx, act=arch.hidden_act
                )
            else:
                out = attn_kernels.sharded_fused_mlp_call(
                    x,
                    p_mlp["gate_proj"]["w"],
                    p_mlp["up_proj"]["w"],
                    p_mlp["down_proj"]["w"],
                    act=arch.hidden_act,
                )
            if out is None:
                raise NotImplementedError(
                    f"mlp_kernel_enabled: MLP shape (act={arch.hidden_act!r}) is "
                    "not kernel-eligible; disable the flag"
                )
            attn_select._record_strategy("mlp_fused_kernel")
            return out
        aq, ac = arch.act_quant, arch.act_clamp
        if arch.hidden_act == "xielu":
            # apertus: per-layer learnable activation scalars ride the scan with
            # the mlp params (p_mlp["xielu"] = {"alpha_p", "alpha_n"}, f32)
            a = p_mlp["xielu"]
            up = xielu(_linear(x, p_mlp["up_proj"], aq, ac, adapter_ids),
                       a["alpha_p"], a["alpha_n"])
            return _linear(up, p_mlp["down_proj"], aq, ac, adapter_ids)
        act = ACT_FNS[arch.hidden_act]
        if not arch.gated_mlp:
            up = act(_linear(x, p_mlp["up_proj"], aq, ac, adapter_ids))
            return _linear(up, p_mlp["down_proj"], aq, ac, adapter_ids)
        gate = act(_linear(x, p_mlp["gate_proj"], aq, ac, adapter_ids))
        up = _linear(x, p_mlp["up_proj"], aq, ac, adapter_ids)
        return _linear(gate * up, p_mlp["down_proj"], aq, ac, adapter_ids)


#: cache inputs of a program whose rows are not whole prompts from position 0
_NOT_WHOLE_PROMPTS = frozenset({"write_positions", "attn_mask", "mixed_row_ids"})


def decoder_layer(
    arch: DecoderArch,
    lp: Dict[str, Any],
    hidden: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    k_cache_l: jax.Array,
    v_cache_l: jax.Array,
    position_ids: jax.Array,
    cache_spec,
    attend_to_cache: bool,
    policy: ShardingPolicy = DEFAULT_POLICY,
    layout=DEFAULT_KV_LAYOUT,
    cache_inputs: Optional[Dict[str, jax.Array]] = None,
    adapter_ids: Optional[jax.Array] = None,
    defer_write: bool = False,
    mlp_stacked=None,
    qkv_stacked=None,
    layer_idx=None,  # GLOBAL layer index (per-layer KV-quant scale rows)
    stacked_layer_idx=None,  # segment-local index into the stacked weights
    tkg_stacked=None,  # (k_s, v_s, kv_len): stacked-cache fused decode kernel
    spec_window=None,  # (k_sp, v_sp, win_pos, slot): draft-window scratch
    moe_tally=None,  # list gaining a routed layer's held-pair count (ops/moe.py)
    moe_stacked=None,  # (gate, up, down): the segment's stacked expert weights
):
    if stacked_layer_idx is None:
        stacked_layer_idx = layer_idx
    if moe_stacked is not None:
        moe_stacked = (*moe_stacked, stacked_layer_idx)
    # per-layer rope selection (gemma3 local/global thetas): cos/sin arrive
    # stacked (2, B, S, D) and the layer flag picks one inside the scan body
    if "use_local_rope" in lp:
        cos = jnp.where(lp["use_local_rope"], cos[1], cos[0])
        sin = jnp.where(lp["use_local_rope"], sin[1], sin[0])
    window_enabled = lp.get("use_sliding_window")
    use_rope = lp.get("use_rope")

    # the attention half of the layer: its input norm and whatever the attention
    # call's own scopes leave; the FFN half: the residual adds, the norms around
    # the feed-forward and what its scopes leave (telemetry/regions.py)
    with jax.named_scope("attn.block"):
        h = hidden if arch.post_block_norm else _norm(arch, hidden, lp["input_layernorm"])
        if "input_norm_skip" in lp:
            # per-layer scalar riding the scan xs: EAGLE drafts feed the fc output
            # straight into attention for their first layer (no input norm)
            h = jnp.where(lp["input_norm_skip"], hidden, h)
        extra = dict(layer_idx=layer_idx)  # the paged pool's layer, KV-quant scale rows
        if arch.mla is not None:
            from nxdi_tpu.ops.mla import mla_attention_block as attn_block_fn
        else:
            attn_block_fn = attention_block
            extra.update(
                defer_write=defer_write, qkv_stacked=qkv_stacked, tkg_stacked=tkg_stacked,
                stacked_layer_idx=stacked_layer_idx, spec_window=spec_window,
            )
        attn_out, (nk, nv) = attn_block_fn(
            arch, lp["attn"], h, cos, sin, k_cache_l, v_cache_l,
            position_ids, cache_spec, attend_to_cache, policy, layout, cache_inputs,
            adapter_ids, window_enabled, use_rope, **extra,
        )

    # a FRESH prefill's rows behind a prompt's last token are bucket padding,
    # which the expert layer's compact form leaves out. Every other program's
    # rows are all read: a speculation verify window (cache-attending, or with
    # write_positions / attn_mask) carries a ``last_token_index`` that is no
    # length, and a packed mixed stream indexes its rows otherwise
    last_real = None
    if not attend_to_cache and cache_inputs and not cache_inputs.keys() & _NOT_WHOLE_PROMPTS:
        last_real = cache_inputs.get("last_token_index")

    def routed(x):
        return moe_ops.moe_block(
            arch, arch.moe, lp["moe"], x, policy.hidden, moe_tally, moe_stacked, last_real
        )

    with jax.named_scope("ffn.block"):
        if arch.parallel_block:
            # cohere / gpt-neox: attention and MLP read their (possibly shared)
            # pre-norms off the SAME residual input, one residual add
            h_mlp = _norm(arch, hidden, lp["post_attention_layernorm"])
            if arch.moe is not None and "moe" in lp:
                ff = routed(h_mlp)
            else:
                ff = mlp_block(arch, lp["mlp"], h_mlp, adapter_ids, mlp_stacked, stacked_layer_idx, policy=policy)
            hidden = hidden + (attn_out + ff) * arch.residual_multiplier
        elif arch.post_block_norm:
            # olmo2: x + norm(attn(x)); x + norm(mlp(x)). K-EXAONE's expert layers
            # alike: on a share the norm takes the held experts' partial sum
            hidden = hidden + _norm(arch, attn_out, lp["input_layernorm"]) * arch.residual_multiplier
            if arch.moe is not None and "moe" in lp:
                ff = routed(hidden)
            else:
                ff = mlp_block(arch, lp["mlp"], hidden, adapter_ids, mlp_stacked, stacked_layer_idx, policy=policy)
            hidden = hidden + _norm(arch, ff, lp["post_attention_layernorm"]) * arch.residual_multiplier
        elif arch.sandwich_norm:
            # gemma lineage: post-norms applied to the block OUTPUT before the
            # residual add, and a dedicated pre-feedforward norm
            # (reference: NeuronGemma3DecoderLayer forward, modeling_gemma3.py:224)
            attn_out = _norm(arch, attn_out, lp["post_attention_layernorm"])
            hidden = hidden + attn_out
            h = _norm(arch, hidden, lp["pre_feedforward_layernorm"])
            # per-layer MoE-vs-dense decided by the params structure so segmented
            # stacks (deepseek-V3 first_k_dense_replace, minimax) mix both
            if arch.moe is not None and "moe" in lp:
                ff = routed(h)
            else:
                ff = mlp_block(arch, lp["mlp"], h, adapter_ids, mlp_stacked, stacked_layer_idx, policy=policy)
            ff = _norm(arch, ff, lp["post_feedforward_layernorm"])
            hidden = hidden + ff
        else:
            hidden = hidden + attn_out * arch.residual_multiplier
            h = _norm(arch, hidden, lp["post_attention_layernorm"])
            if arch.moe is not None and "moe" in lp:
                hidden = hidden + routed(h) * arch.residual_multiplier
            else:
                hidden = hidden + mlp_block(arch, lp["mlp"], h, adapter_ids, mlp_stacked, stacked_layer_idx, policy=policy) * arch.residual_multiplier
    hidden = constrain(hidden, policy.hidden)
    return hidden, (nk, nv)


def _pipelined_decoder_layers(
    arch, layer_params, hidden, cos, sin, cache, position_ids, step_fn,
    cache_inputs, adapter_ids, defer=False, policy=DEFAULT_POLICY,
    collect_hidden=False,
):
    """GPipe-style pipeline over the ``pp`` mesh axis.

    TPU-native pipeline parallel (reference: pp_degree through the NxD
    ModelBuilder, models/config.py:366, application_base.py:158-163 — the
    reference delegates the schedule to its builder; here it is explicit).
    Mechanism: ``shard_map`` manual over ``pp`` only (tp/ep/... stay under
    GSPMD), the layer-stacked params and the cache sharded on their leading
    layer dim so each stage owns a contiguous slice of layers + stage-local
    KV. The batch splits into M microbatches (``pp_microbatches`` deepens the
    split to shrink the bubble); for ``T = M + pp - 1`` ticks each stage
    scans its local layers over its current microbatch and hands the
    activations to the next stage with a ring ``ppermute`` — collectives
    ride ICI, bubble fraction (pp-1)/(M+pp-1).

    ``defer`` (decode hot path, round-2 weak #2): the scan emits only fresh
    K/V rows and each tick lands them with ONE stage-local in-place commit
    (the Pallas commit kernel addressed by microbatch line via seq-id
    routing) instead of round-tripping the stage's whole cache through the
    scan ys per tick. Bubble ticks commit with slot -1 (dropped).

    Non-deferred bubble ticks still compute (SPMD requires it) but write
    back the old cache values, so garbage never lands.
    """
    mesh = jax.sharding.get_abstract_mesh()
    pp = arch.pp_degree
    n_micro = arch.pp_microbatches or pp
    B = hidden.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible by pp microbatches {n_micro}")
    mb = B // n_micro
    ci = cache_inputs or {}
    cos_baxis = 0 if cos.ndim == 3 else 1  # stacked rope variants: (2, B, S, D)

    def slice_b(x, i, axis=0):
        return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis)

    def staged(params_local, k_local, v_local, hidden_all, cos_, sin_, pos_, ci_, ad_):
        stage = jax.lax.axis_index(AXIS_PP)

        def scan_body(mb_ctx):
            cos_m, sin_m, pos_m, ci_m, ad_m = mb_ctx

            def body(h, xs):
                lp, kl, vl = xs
                h, nk, nv = step_fn(
                    h, lp, kl, vl, cos_m, sin_m, pos_m, ci_m, ad_m, defer_=defer
                )
                return h, ((nk, nv, h) if collect_hidden else (nk, nv))

            return body

        def tick(t, carry):
            h, out, kl, vl, out_h = carry
            i = t - stage  # this stage's microbatch index at tick t
            i_c = jnp.clip(i, 0, n_micro - 1)
            valid = (i >= 0) & (i < n_micro)
            ctx = (
                slice_b(cos_, i_c, cos_baxis),
                slice_b(sin_, i_c, cos_baxis),
                slice_b(pos_, i_c),
                {k: slice_b(v, i_c) for k, v in ci_.items()},
                None if ad_ is None else slice_b(ad_, i_c),
            )
            k_mb = jax.lax.dynamic_slice_in_dim(kl, i_c * mb, mb, axis=1)
            v_mb = jax.lax.dynamic_slice_in_dim(vl, i_c * mb, mb, axis=1)
            if collect_hidden:
                h_out, (k_new, v_new, h_layers) = jax.lax.scan(
                    scan_body(ctx), h, (params_local, k_mb, v_mb)
                )
                # bank this stage's per-layer hiddens for microbatch i
                banked_h = jax.lax.dynamic_update_slice_in_dim(
                    out_h, h_layers[None], i_c, 0
                )
                out_h = jnp.where(valid, banked_h, out_h)
            else:
                h_out, (k_new, v_new) = jax.lax.scan(
                    scan_body(ctx), h, (params_local, k_mb, v_mb)
                )
            if defer:
                # k_new/v_new are FRESH ROWS (L_local, mb, KV, 1, D): land
                # them in the stage-local cache with one in-place commit at
                # the microbatch's cache lines; bubble ticks drop (slot -1).
                # Inside the pp-manual region the cache is STILL GSPMD-sharded
                # over the kv-head axes — the pallas call must run per kv
                # shard (a raw custom call would force the partitioner to
                # gather the stage cache every tick), so it nests a shard_map
                # over exactly those axes.
                from nxdi_tpu.ops.kernels import kv_commit

                pos_mb = slice_b(pos_, i_c).astype(jnp.int32)  # (mb, 1)
                slots = jnp.where(valid, pos_mb, -1)
                lines = i_c * mb + jnp.arange(mb, dtype=jnp.int32)
                if kv_commit.commit_rows_supported(
                    kl.shape, vl.shape, k_new.shape, v_new.shape
                ):
                    kv_ax = policy.cache_kv[1]
                    axes = tuple(
                        a for a in (
                            kv_ax if isinstance(kv_ax, (tuple, list)) else (kv_ax,)
                        )
                        if a is not None and a in mesh.axis_names
                    )
                    kr = k_new.astype(kl.dtype)
                    vr = v_new.astype(vl.dtype)
                    if axes:
                        cspec = P(None, None, kv_ax, None, None)
                        commit = jax.shard_map(
                            kv_commit.kv_commit_rows,
                            # the CONTEXT mesh (pp already manual here)
                            mesh=jax.sharding.get_abstract_mesh(),
                            in_specs=(cspec, cspec, cspec, cspec, P(None, None),
                                      P(None)),
                            out_specs=(cspec, cspec),
                            axis_names=set(axes),
                            # check_vma must be off: the commit kernel's
                            # aliased (donated) cache outputs carry the
                            # UNREDUCED vma of their inputs, and shard_map's
                            # varying-manual-axes check rejects the alias
                            # pair even though each shard only ever writes
                            # its own rows (replicated-slot semantics are
                            # preserved by construction — every shard gets
                            # identical slots/lines inputs)
                            check_vma=False,
                        )
                        kl, vl = commit(kl, vl, kr, vr, slots, lines)
                    else:
                        kl, vl = kv_commit.kv_commit_rows(kl, vl, kr, vr, slots, lines)
                else:
                    b_idx = lines[:, None]
                    sl = jnp.where(slots < 0, kl.shape[3], slots)

                    def put(cache_arr, rows):
                        vals = rows.astype(cache_arr.dtype).swapaxes(2, 3)

                        def per_layer(cl, rl):
                            return cl.at[b_idx, :, sl].set(rl, mode="drop")

                        return jax.vmap(per_layer)(cache_arr, vals)

                    kl, vl = put(kl, k_new), put(vl, v_new)
            else:
                # bubble ticks write back the old values (no-op update)
                k_new = jnp.where(valid, k_new, k_mb)
                v_new = jnp.where(valid, v_new, v_mb)
                kl = jax.lax.dynamic_update_slice_in_dim(kl, k_new, i_c * mb, axis=1)
                vl = jax.lax.dynamic_update_slice_in_dim(vl, v_new, i_c * mb, axis=1)
            # the last stage banks finished microbatches
            banked = jax.lax.dynamic_update_slice_in_dim(out, h_out[None], i_c, 0)
            out = jnp.where(valid & (stage == pp - 1), banked, out)
            # ring-shift activations to the next stage; stage 0 feeds the
            # next microbatch from the embedded input
            h_next = jax.lax.ppermute(
                h_out, AXIS_PP, [(s, (s + 1) % pp) for s in range(pp)]
            )
            feed = slice_b(hidden_all, jnp.clip(t + 1, 0, n_micro - 1))
            h = jnp.where(stage == 0, feed, h_next)
            return h, out, kl, vl, out_h

        h0 = slice_b(hidden_all, 0)
        out0 = jnp.zeros((n_micro,) + h0.shape, h0.dtype)
        n_local = jax.tree_util.tree_leaves(params_local)[0].shape[0]
        out_h0 = jnp.zeros((n_micro, n_local) + h0.shape, h0.dtype)
        h_fin, out, k_fin, v_fin, out_h = jax.lax.fori_loop(
            0, n_micro + pp - 1, tick, (h0, out0, k_local, v_local, out_h0)
        )
        # replicate the last stage's banked outputs to every stage
        out = jax.lax.psum(
            jnp.where(stage == pp - 1, out, jnp.zeros_like(out)), AXIS_PP
        )
        # (n_micro, L_local, mb, S, H) -> (L_local, n_micro, mb, S, H): the
        # layer axis leads so the pp out-spec stacks stages into global order
        return out, k_fin, v_fin, jnp.swapaxes(out_h, 0, 1)

    p_specs = jax.tree_util.tree_map(lambda _: P(AXIS_PP), layer_params)
    ci_specs = {k: P() for k in ci}
    out, new_k, new_v, out_h = jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(p_specs, P(AXIS_PP), P(AXIS_PP), P(), P(), P(), P(), ci_specs,
                  P() if adapter_ids is not None else None),
        out_specs=(P(), P(AXIS_PP), P(AXIS_PP), P(AXIS_PP)),
        axis_names={AXIS_PP},
        # check_vma off by necessity, not convenience: the GPipe body emits
        # `out` with out_specs=P() (replicated) but its value is only
        # meaningful on the LAST stage (earlier stages hold bubble garbage);
        # the ppermute ring then delivers the real rows. The vma checker
        # would demand a psum/all_gather to "prove" replication — a real
        # collective round the schedule neither needs nor wants. The
        # invariant (stage s's tick t output is consumed only by stage s+1
        # at tick t+1) is enforced by the ppermute wiring itself and
        # token-matched under pp in tests/integration/test_parallelism.py.
        check_vma=False,
    )(layer_params, cache["k"], cache["v"], hidden, cos, sin, position_ids, ci,
      adapter_ids)
    hidden_out = out.reshape((B,) + out.shape[2:])
    new_cache = {"k": new_k, "v": new_v}
    if collect_hidden:
        # (L, n_micro, mb, S, H) -> (L, B, S, H): microbatch i holds batch
        # rows [i*mb, (i+1)*mb) — contiguous, so a reshape reassembles
        L = out_h.shape[0]
        layer_h = out_h.reshape((L, B) + out_h.shape[3:])
        return hidden_out, new_cache, layer_h
    return hidden_out, new_cache


def _interleaved_window_scan(
    arch, layer_params, hidden, cos, sin, cache, position_ids, cache_spec,
    step_fn, defer, layout, policy, cache_inputs, adapter_ids,
    collect_hidden, layer_injections,
):
    """Unit scan over interleaved full/sliding-window layer stacks.

    TPU-native form of the reference's per-layer window-sized caches
    (gpt_oss_kv_cache_manager.py [403 LoC]; kv_cache_manager.py:195-210):
    full-attention layers read/write the full-length ``cache['k']/['v']``
    stack; sliding-window layers a W-slot ring stack ``['k_win']/['v_win']``
    (kvcache WindowKVLayout semantics). A single lax.scan cannot carry xs of
    two different sequence lengths, so the scan runs over the pattern's
    smallest REPEATING UNIT (gpt-oss [SWA, full] -> period 2; gemma3 5 local
    + 1 global -> period 6): one compiled body per unit position, L/period
    scan steps — compile cost grows with the pattern period, not the depth.

    Window kinds are STATIC per unit position, so sliding-window masks
    compile directly (no traced per-layer flag is needed, though flags
    riding the params stay correct). Deferred-write decode emits fresh rows
    per kind; commits land separately (ring rows at slot ``pos % W``).
    """
    from nxdi_tpu.kvcache.kv_cache import WindowKVLayout

    pat = arch.kv_window_pattern
    if pat is None or len(pat) != arch.num_layers:
        raise ValueError(
            "cache carries a k_win ring stack but arch.kv_window_pattern is "
            f"unset or mismatched (pattern {pat}, layers {arch.num_layers})"
        )
    if isinstance(layer_params, (list, tuple)):
        raise NotImplementedError(
            "interleaved window-sized KV requires a homogeneous layer stack"
        )
    p = arch.kv_pattern_period
    U = arch.num_layers // p
    f_idx = [j for j in range(p) if not pat[j]]
    w_idx = [j for j in range(p) if pat[j]]
    assert f_idx and w_idx, "cache split requires both full and window layers"
    win_layout = WindowKVLayout(
        window=cache["k_win"].shape[3],
        route_by_seq_id=getattr(layout, "route_by_seq_id", False),
    )

    def unit(x):
        return x.reshape((U, x.shape[0] // U) + x.shape[1:])

    unit_params = jax.tree_util.tree_map(unit, layer_params)
    kf, vf = unit(cache["k"]), unit(cache["v"])
    kw, vw = unit(cache["k_win"]), unit(cache["v_win"])
    inj_u = unit(layer_injections) if layer_injections is not None else None

    def unit_body(h, xs):
        lp_u, kf_u, vf_u, kw_u, vw_u, inj_unit = xs
        rows_f, rows_w, hs = [], [], []
        fi = wi = 0
        for j in range(p):
            lp = jax.tree_util.tree_map(lambda x: x[j], lp_u)
            if pat[j]:
                h, nk, nv = step_fn(
                    h, lp, kw_u[wi], vw_u[wi], cos, sin, position_ids,
                    cache_inputs, adapter_ids,
                    layout_=win_layout, windowable_=False,
                )
                rows_w.append((nk, nv))
                wi += 1
            else:
                h, nk, nv = step_fn(
                    h, lp, kf_u[fi], vf_u[fi], cos, sin, position_ids,
                    cache_inputs, adapter_ids,
                )
                rows_f.append((nk, nv))
                fi += 1
            if inj_unit is not None:  # deepstack: per-layer residual adds
                h = h + inj_unit[j].astype(h.dtype)
            if collect_hidden:
                hs.append(h)

        def stack(rows):
            return (
                jnp.stack([r[0] for r in rows]),
                jnp.stack([r[1] for r in rows]),
            )

        ys = (stack(rows_f), stack(rows_w))
        if collect_hidden:
            ys = ys + (jnp.stack(hs),)  # (p, B, S, hidden), layer order
        return h, ys

    hidden, ys_all = jax.lax.scan(
        unit_body, hidden, (unit_params, kf, vf, kw, vw, inj_u)
    )
    (ys_kf, ys_vf), (ys_kw, ys_vw) = ys_all[0], ys_all[1]

    def flat(y):  # (U, per_unit, ...) -> (L_kind, ...)
        return y.reshape((-1,) + y.shape[2:])

    if defer:
        ci_commit = dict(cache_inputs or {})
        ci_commit["position_ids"] = position_ids
        full_new = layout.commit_rows(
            {"k": cache["k"], "v": cache["v"]},
            flat(ys_kf), flat(ys_vf), ci_commit, cache_spec, policy=policy,
        )
        win_new = win_layout.commit_rows(
            {"k": cache["k_win"], "v": cache["v_win"]},
            flat(ys_kw), flat(ys_vw), ci_commit, cache_spec, policy=policy,
        )
    else:
        full_new = {"k": flat(ys_kf), "v": flat(ys_vf)}
        win_new = {"k": flat(ys_kw), "v": flat(ys_vw)}
    new_cache = {
        "k": full_new["k"],
        "v": full_new["v"],
        "k_win": win_new["k"],
        "v_win": win_new["v"],
    }
    if collect_hidden:
        # (U, p, B, S, hidden) -> (L, B, S, hidden) in global layer order
        layer_h = ys_all[2].reshape((-1,) + ys_all[2].shape[2:])
        return hidden, new_cache, layer_h
    return hidden, new_cache


def _extract_stacked_weights(arch: DecoderArch, seg, rows: int):
    """Pull the layer-stacked MLP / fused-QKV weights out of a segment pytree
    when their Pallas kernels are enabled, so the scan does not slice them
    per layer (see run_decoder_layers). Returns (seg', mlp_stacked,
    qkv_stacked, moe_stacked) — stacked entries are None when the kernel is
    off or the segment has no such weights (e.g. a MoE segment).

    ``moe_stacked``: a routed segment's plain expert weights where the expert
    layer takes its sorted or its compact form at this program's ``rows`` (B x
    S; ops/moe.py ``expert_form``). The TPU's grouped matmul is such a kernel
    too (``_grouped_matmul``): it takes the whole stack and the layer's
    index. So do the compact form's einsums, which run inside a loop of their
    own (``_compact_expert_ffn``). The dense form's einsums read a layer's
    slice of the xs in place."""
    mlp_st = qkv_st = moe_st = None
    names = ("gate_proj", "up_proj", "down_proj")
    moe = seg.get("moe") if isinstance(seg, dict) else None
    if (
        arch.moe is not None
        and not arch.moe.per_phase_hybrid
        and isinstance(moe, dict)
        and isinstance(moe.get("experts"), dict)
        and all(  # plain weights: a quantized leaf is dequantized a layer at a time
            isinstance(moe["experts"].get(k), dict) and "w" in moe["experts"][k] for k in names
        )
        and moe_ops.expert_form(arch.moe, rows) != "dense"
    ):
        experts = {k: dict(moe["experts"][k]) for k in names}
        moe_st = tuple(experts[k].pop("w") for k in names)
        seg = {**seg, "moe": {**moe, "experts": {**moe["experts"], **experts}}}
    if (
        arch.mlp_kernel_enabled
        and isinstance(seg, dict)
        and isinstance(seg.get("mlp"), dict)
        and all(
            isinstance(seg["mlp"].get(k), dict) and "w" in seg["mlp"][k]
            for k in ("gate_proj", "up_proj", "down_proj")
        )
    ):
        mlp = {k: dict(v) if isinstance(v, dict) else v for k, v in seg["mlp"].items()}
        mlp_st = (
            mlp["gate_proj"].pop("w"),
            mlp["up_proj"].pop("w"),
            mlp["down_proj"].pop("w"),
        )
        seg = {**seg, "mlp": mlp}
    if (
        arch.qkv_kernel_enabled
        and isinstance(seg, dict)
        and isinstance(seg.get("attn"), dict)
        and isinstance(seg["attn"].get("qkv_proj"), dict)
        and "w" in seg["attn"]["qkv_proj"]
    ):
        attn = dict(seg["attn"])
        qp = dict(attn["qkv_proj"])
        qkv_st = (qp.pop("w"), qp.pop("b", None))
        attn["qkv_proj"] = qp
        seg = {**seg, "attn": attn}
    return seg, mlp_st, qkv_st, moe_st


def run_decoder_layers(
    arch: DecoderArch,
    layer_params: Dict[str, Any],  # layer-stacked pytree
    hidden: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    cache: Dict[str, jax.Array],  # full (L, B, KV, S_max, D)
    position_ids: jax.Array,
    cache_spec: KVCacheSpec,
    attend_to_cache: bool,
    kv_window: Optional[int] = None,
    policy: ShardingPolicy = DEFAULT_POLICY,
    layout=DEFAULT_KV_LAYOUT,
    cache_inputs: Optional[Dict[str, jax.Array]] = None,
    collect_hidden: bool = False,
    adapter_ids: Optional[jax.Array] = None,
    layer_injections: Optional[jax.Array] = None,  # (L, B, S, hidden) or None
    layer_replacements: Optional[Tuple[jax.Array, jax.Array]] = None,
    spec_window_inputs: Optional[Tuple[jax.Array, jax.Array]] = None,
    moe_held_tally: Optional[list] = None,
    first_layer: Optional[int] = None,
):
    """Scan the layer stack.

    ``moe_held_tally``: a list that gains ONE ``(pair, slot_rows)``: the int32
    pair of the (row, expert) pairs of this forward that fell on held experts,
    summed over the routed layers, and the number of routed layers; and, where
    the expert layers take their compact form (ops/moe.py ``expert_form``; a
    share's larger prefill buckets), the slot rows their matmuls multiplied,
    else None. Both ride the scan's carry (beside the pool, where the pool is
    carried); ``held_pair_outputs`` names them for a step program's outputs.

    ``first_layer``: the segments are layers ``first_layer ..`` of a stack
    that ``cache`` holds WHOLE and that other calls walk too (mimo-v2: two
    kinds of layer interleaved in depth, each kind with a stack of its own).
    The paged pool is addressed from that layer on, in place as ever. Any
    other stack (ring rows a slot) is read in place at the layer's index and
    NOT written here: the call attends cache plus fresh rows and hands back
    ``{"k_rows", "v_rows"}``, the layers' fresh rows, for the caller's ONE
    commit over the whole stack after the last call (a slice of the stack as
    the scan's xs, or a per-layer write as its ys, would copy the stack).

    Where the cache rides: the paged pool (``BlockKVLayout``) is the scan's
    CARRY beside the hidden state — every layer writes its rows at
    ``(layer, slot)`` and the paged kernels read at ``(layer, block)``, so
    the donated pool stays one buffer from the program's input to its
    aliased output and is never an xs or a ys (a pool slice as xs/ys cost
    five whole-pool passes a step: two copies, two slices, two stackings).
    The contiguous decode path emits fresh rows as ys and commits once after
    the scan (``defer``); the other layouts hand per-layer cache slices
    through as xs/ys.

    ``spec_window_inputs`` (win_pos (B, W), slot ()): engaged when the cache
    pytree carries ``k_spec``/``v_spec`` scratch stacks (the fused-speculation
    draft loop, speculation/fused.py) — fresh rows land in the scratch, the
    full cache is read-only, and the window commits ONCE after the draft scan.

    ``layer_replacements``: ((L, B, S, hidden) values, (L,) mask) — layers
    whose mask entry is nonzero have their output stream REPLACED by the
    given value (tensor-replacement debugging, the capture plumbing in
    reverse; reference: utils/tensor_replacement/registry.py). Homogeneous
    single-lap stacks only.

    ``layer_injections``: per-layer residual additions applied AFTER each
    layer (qwen3-vl deepstack: vision features summed into the first K
    layers' outputs at visual positions — reference: _deepstack_process).

    ``kv_window`` statically truncates the attended cache to the bucket's token
    budget (reference: per-bucket compiled TKG programs attend only bucket-many
    positions) while writes still target the full-length cache. Contiguous
    layout only — the block layout's window is its block-table width.

    ``collect_hidden`` additionally stacks each layer's output hidden state as
    scan ys — (L, B, S, hidden) — for EAGLE3's aux-feature taps (reference:
    model_base.py:1581). Costs L×B×S×H activation memory, so only submodels
    that need it compile with it; returns a 3-tuple then.
    """

    from nxdi_tpu.kvcache.kv_cache import WindowKVLayout

    # bucket re-windowing slices the cache S dim — meaningless for the paged
    # pool and for the ring layout (its S dim is slots, not positions)
    paged = isinstance(layout, BlockKVLayout)
    windowable = not isinstance(layout, (BlockKVLayout, WindowKVLayout))
    spec_mode = "k_spec" in cache
    # Heterogeneous stacks (deepseek-V3 first_k_dense_replace, minimax) arrive
    # as a LIST of layer-stacked segments — e.g. [dense-MLP head, MoE rest] —
    # each scanned over its static slice of the cache. Homogeneous models pass
    # the single stacked pytree unchanged.
    segments = (
        list(layer_params) if isinstance(layer_params, (list, tuple)) else [layer_params]
    )
    # what the attention table (ops/attention_select.py) will answer this
    # stack's layers, asked once for the stack's two decisions
    stack_site = attn_select.site_of(
        arch, layout, policy, cache_inputs,
        (position_ids.shape[0], arch.num_attention_heads, position_ids.shape[1], arch.head_dim),
        None,
        jax.ShapeDtypeStruct(cache["k"].shape[0 if paged else 1:], cache["k"].dtype),
        cache_spec.compute_dtype,
        attend_to_cache=attend_to_cache, deferred=True, stacked=True, spec_window=spec_mode,
        layer_flags=tuple(
            any(isinstance(sg, dict) and flag in sg for sg in segments)
            for flag in ("use_sliding_window", "use_rope")
        ),
    )
    # deferred cache writes (decode hot path): the scan emits only fresh K/V
    # rows; they commit in ONE scatter on the stacked cache below — carrying
    # full cache slices through the scan as ys round-trips the whole cache
    # per layer (measured ~6x the pure-attention cost on v5e)
    shared = first_layer is not None and not paged
    if shared and not attend_to_cache:
        raise NotImplementedError(
            "a shared per-slot stack is read here, never written: its prefill "
            "rows are the caller's to commit"
        )
    defer = attn_select.defers(stack_site) or shared
    # stacked-cache fused TKG kernel (round-4): the kernel reads the OLD cache
    # from the full stack via scalar-prefetched layer index, so the scan's
    # per-layer cache slices are never pallas operands (round-3's slice-copy
    # tax) and the kv_window slice is skipped: where a layer would not take
    # it, skipping the slice would regress the XLA path to the full cache
    use_stacked_tkg = (
        defer and attn_select.select(stack_site, record=False) == "tkg_fused_kernel_stacked"
    )
    if spec_mode and (
        not attend_to_cache
        or arch.pp_degree > 1
        or arch.mla is not None
        or "k_win" in cache
        or not isinstance(layout, ContiguousKVLayout)
        or (cache_inputs or {}).get("attn_mask") is not None
        or spec_window_inputs is None
    ):
        raise NotImplementedError(
            "the speculation-window scratch rides the plain contiguous decode "
            "path only (speculation/fused.py gates eligibility)"
        )

    def _step(h, lp, kl, vl, cos_, sin_, pos_, ci_, ad_, layout_=None,
              windowable_=None, defer_=None, mlp_stacked=None,
              qkv_stacked=None, layer_idx=None, stacked_layer_idx=None,
              tkg_stacked=None, spec_window=None, moe_tally=None, moe_stacked=None):
        """One decoder layer with the bucket's static KV window applied.
        ``layout_``/``windowable_``/``defer_`` override the stack-wide
        defaults for the interleaved-window unit scan (ring slices use the
        ring layout) and the pipelined path (stage-local deferred commit)."""
        lay = layout if layout_ is None else layout_
        win_ok = windowable if windowable_ is None else windowable_
        dfr = defer if defer_ is None else defer_
        if spec_window is not None:
            # the scratch IS the write target: ys carry its updated slices
            # (the same plumbing as deferred fresh rows), commit happens once
            # in the caller
            dfr = True
        stk = dict(mlp_stacked=mlp_stacked, qkv_stacked=qkv_stacked,
                   layer_idx=layer_idx, stacked_layer_idx=stacked_layer_idx,
                   tkg_stacked=tkg_stacked, spec_window=spec_window,
                   moe_tally=moe_tally, moe_stacked=moe_stacked)
        if (win_ok and kv_window is not None and kv_window < kl.shape[2]
                and attend_to_cache and tkg_stacked is None):
            k_win, v_win = kl[:, :, :kv_window], vl[:, :, :kv_window]
            h, (nkw, nvw) = decoder_layer(
                arch, lp, h, cos_, sin_, k_win, v_win, pos_, cache_spec,
                attend_to_cache, policy, lay, ci_, ad_, defer_write=dfr, **stk,
            )
            if dfr:
                nk, nv = nkw, nvw  # fresh rows, committed after the scan
            else:
                nk = jax.lax.dynamic_update_slice(kl, nkw, (0, 0, 0, 0))
                nv = jax.lax.dynamic_update_slice(vl, nvw, (0, 0, 0, 0))
        else:
            h, (nk, nv) = decoder_layer(
                arch, lp, h, cos_, sin_, kl, vl, pos_, cache_spec,
                attend_to_cache, policy, lay, ci_, ad_, defer_write=dfr, **stk,
            )
        return h, nk, nv

    if arch.pp_degree > 1:
        if layer_injections is not None:
            raise NotImplementedError(
                "deepstack layer injections are not supported under "
                "pipeline parallel"
            )
        if layer_replacements is not None:
            raise NotImplementedError(
                "tensor replacement at layer outputs is not supported under "
                "pipeline parallel — bisect on a tp-only config"
            )
        # deferred commit applies under pp too (stage-local in-place commit
        # each tick; see _pipelined_decoder_layers) — decode-shaped only
        # (unquantized, unrouted cache rows written at their own positions: the
        # stage-local commit kernel's terms)
        defer_pp = (
            defer
            and stack_site.phase == "decode"
            and stack_site.raw_cache
            and "write_positions" not in stack_site.needs
        )
        # Heterogeneous segment stacks (deepseek-V3 first_k_dense + MoE rest,
        # minimax) pipeline as MULTI-LAP virtual stages: each segment runs one
        # full GPipe rotation over the pp mesh (stage s holds each segment's
        # s-th layer slice — the looping-pipeline schedule), activations carry
        # between laps (reference analog: generation_minimax_m2_pp_demo.py).
        # Cost: one bubble set per segment.
        pks, pvs, phs = [], [], []
        off_pp = 0
        for seg in segments:
            n_seg = jax.tree_util.tree_leaves(seg)[0].shape[0]
            if n_seg % arch.pp_degree:
                raise ValueError(
                    f"segment of {n_seg} layers is not divisible by pp_degree "
                    f"({arch.pp_degree}) — each pipeline lap needs equal "
                    "per-stage layer slices"
                )
            seg_cache = {
                "k": jax.lax.slice_in_dim(cache["k"], off_pp, off_pp + n_seg, axis=0),
                "v": jax.lax.slice_in_dim(cache["v"], off_pp, off_pp + n_seg, axis=0),
            }
            res = _pipelined_decoder_layers(
                arch, seg, hidden, cos, sin, seg_cache, position_ids,
                _step, cache_inputs, adapter_ids, defer=defer_pp,
                policy=policy, collect_hidden=collect_hidden,
            )
            if collect_hidden:
                hidden, seg_new, seg_h = res
                phs.append(seg_h)
            else:
                hidden, seg_new = res
            pks.append(seg_new["k"])
            pvs.append(seg_new["v"])
            off_pp += n_seg
        cat_pp = (lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0))
        new_cache = {"k": cat_pp(pks), "v": cat_pp(pvs)}
        if collect_hidden:
            return hidden, new_cache, cat_pp(phs)
        return hidden, new_cache

    if "k_win" in cache:
        if layer_replacements is not None:
            raise NotImplementedError(
                "tensor replacement at layer outputs is not supported with "
                "interleaved window KV stacks — bisect with a full-attention "
                "cache layout"
            )
        return _interleaved_window_scan(
            arch, layer_params, hidden, cos, sin, cache, position_ids,
            cache_spec, _step, defer, layout, policy, cache_inputs,
            adapter_ids, collect_hidden, layer_injections,
        )

    ks, vs, hs = [], [], []
    k_pool, v_pool = (cache["k"], cache["v"]) if paged else (None, None)
    count_held = moe_held_tally is not None
    rows = hidden.shape[0] * hidden.shape[1]
    # ([held pairs, routed layers], slot rows where the expert layers are compact)
    held_pairs = (jnp.zeros((2,), jnp.int32), None)
    if count_held and moe_ops.expert_form(arch.moe, rows) == "compact":
        held_pairs = (held_pairs[0], jnp.int32(0))
    off = first_layer or 0
    for seg in segments:
        # kernel-stacked weights: keep the big MLP/QKV weights OUT of the
        # scanned xs (a pallas operand on a scan slice materializes a full
        # per-layer weight copy) — the kernels index the stacked arrays via
        # scalar-prefetched layer index instead
        seg, mlp_st, qkv_st, moe_st = _extract_stacked_weights(arch, seg, rows)
        n_seg = jax.tree_util.tree_leaves(seg)[0].shape[0]

        def body(carry, xs, mlp_st=mlp_st, qkv_st=qkv_st, moe_st=moe_st, seg_off=off,
                 tkg_st=None):
            # xs carries the GLOBAL layer index (for per-layer KV-quant scale
            # rows, kv_cache._scale_for, and the paged pool's layer); the
            # per-SEGMENT stacked kernel weights index with the segment-local
            # offset
            lp, kl, vl, ksp, vsp, inj, li, repl = xs
            held = layer_tally = None
            if count_held:
                carry, held = carry
                layer_tally = []
            if paged:
                h, kl, vl = carry  # the whole pool, addressed at ``li``
            else:
                h = carry
            if shared:  # the layer's rows of the whole stack, read in place
                with jax.named_scope("kv.read"):
                    kl = jax.lax.dynamic_index_in_dim(cache["k"], li, 0, keepdims=False)
                    vl = jax.lax.dynamic_index_in_dim(cache["v"], li, 0, keepdims=False)
            li_local = li - jnp.int32(seg_off)
            spec_win = None
            if ksp is not None:
                spec_win = (ksp, vsp) + spec_window_inputs
            h, nk, nv = _step(
                h, lp, kl, vl, cos, sin, position_ids, cache_inputs,
                adapter_ids, mlp_stacked=mlp_st, qkv_stacked=qkv_st, moe_stacked=moe_st,
                layer_idx=li, stacked_layer_idx=li_local, tkg_stacked=tkg_st,
                spec_window=spec_win, moe_tally=layer_tally,
            )
            if inj is not None:
                h = h + inj.astype(h.dtype)
            if repl is not None:
                rv, rm = repl
                h = jnp.where(rm > 0, rv.astype(h.dtype), h)
            if paged:
                out, ys = (h, nk, nv), (h if collect_hidden else None)
            else:
                out, ys = h, ((nk, nv, h) if collect_hidden else (nk, nv))
            if count_held:
                pair, slot_rows = held
                pair = pair + jnp.stack(
                    [sum((p for p, _ in layer_tally), jnp.int32(0)), jnp.int32(len(layer_tally))]
                )
                if slot_rows is not None:
                    slot_rows = sum((r for _, r in layer_tally), slot_rows)
                out = (out, (pair, slot_rows))
            return out, ys

        k_seg = v_seg = None
        if not paged and not shared:
            with jax.named_scope("layers"):
                k_seg = jax.lax.slice_in_dim(cache["k"], off, off + n_seg, axis=0)
                v_seg = jax.lax.slice_in_dim(cache["v"], off, off + n_seg, axis=0)
        ksp_seg = vsp_seg = None
        if spec_mode:
            ksp_seg = jax.lax.slice_in_dim(cache["k_spec"], off, off + n_seg, axis=0)
            vsp_seg = jax.lax.slice_in_dim(cache["v_spec"], off, off + n_seg, axis=0)
        if use_stacked_tkg:
            from functools import partial as _partial

            body = _partial(body, tkg_st=(k_seg, v_seg, kv_window))
        inj_seg = (
            jax.lax.slice_in_dim(layer_injections, off, off + n_seg, axis=0)
            if layer_injections is not None
            else None
        )
        repl_seg = (
            (
                jax.lax.slice_in_dim(layer_replacements[0], off, off + n_seg, axis=0),
                jax.lax.slice_in_dim(layer_replacements[1], off, off + n_seg, axis=0),
            )
            if layer_replacements is not None
            else None
        )
        xs = (seg, k_seg, v_seg, ksp_seg, vsp_seg, inj_seg,
              off + jnp.arange(n_seg, dtype=jnp.int32), repl_seg)
        with jax.named_scope("layers"):
            carry = (hidden, k_pool, v_pool) if paged else hidden
            carry, ys = jax.lax.scan(body, (carry, held_pairs) if count_held else carry, xs)
            if count_held:
                carry, held_pairs = carry
            if paged:
                (hidden, k_pool, v_pool), seg_h = carry, ys
            else:
                hidden = carry
        off += n_seg
        if paged:
            hs.append(seg_h)
        elif collect_hidden:
            ks.append(ys[0]); vs.append(ys[1]); hs.append(ys[2])
        else:
            ks.append(ys[0]); vs.append(ys[1])
    cat = (lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0))
    if count_held:
        moe_held_tally.append(held_pairs)
    if paged:
        new_cache = {"k": k_pool, "v": v_pool}
    elif shared:
        new_cache = {"k_rows": cat(ks), "v_rows": cat(vs)}
    elif spec_mode:
        # full cache untouched; the scratch stacks carry this step's rows and
        # the whole window commits once, after the draft scan (fused.py)
        new_cache = {
            "k": cache["k"],
            "v": cache["v"],
            "k_spec": cat(ks),
            "v_spec": cat(vs),
        }
    elif defer:
        ci_commit = dict(cache_inputs or {})
        ci_commit["position_ids"] = position_ids
        with jax.named_scope("kv.write"):
            new_cache = layout.commit_rows(
                cache, cat(ks), cat(vs), ci_commit, cache_spec, policy=policy
            )
    else:
        new_cache = {"k": cat(ks), "v": cat(vs)}
    if collect_hidden:
        return hidden, new_cache, cat(hs)
    return hidden, new_cache


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------

# the layout-input keys every KV layout may consume (ContiguousKVLayout /
# BlockKVLayout / WindowKVLayout .get what they need); single source of truth
# for causal_lm_forward and the custom family forwards (e.g. mimo_v2)
CACHE_INPUT_KEYS = ("seq_ids", "slot_mapping", "block_table",
                    "write_positions", "attn_mask", "last_token_index",
                    "mixed_row_ids")


def collect_cache_inputs(batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: batch[k] for k in CACHE_INPUT_KEYS if k in batch}


def kind_schedule(kinds) -> Tuple[Tuple[str, int, int, int], ...]:
    """The depth-ordered walk of a model whose layers are of several kinds,
    each kind with a layer stack of its own (its params and its cache): one
    entry a run of layers of one kind, ``(kind, lo, hi, segment)`` with
    ``[lo, hi)`` the kind's own layer indices and ``segment`` the run's index
    among the params' stacked segments."""
    schedule, counters, prev = [], {}, None
    for kind in kinds:
        lo = counters.get(kind, 0)
        if kind == prev:
            t, a, b, s = schedule[-1]
            schedule[-1] = (t, a, b + 1, s)
        else:
            schedule.append((kind, lo, lo + 1, len(schedule)))
            prev = kind
        counters[kind] = lo + 1
    return tuple(schedule)


def held_pair_outputs(tallies, prefill: bool) -> Dict[str, jax.Array]:
    """What a share's step program returns beside its tokens, from what
    ``run_decoder_layers`` tallied (``moe_held_tally``: one entry a call, summed
    here): token generation its held pairs and routed layers, a prefill with
    compact experts its live pairs and the slot rows multiplied for them."""
    pairs, layers = sum((p for p, _ in tallies[1:]), tallies[0][0])
    if not prefill:
        return {"moe_held_pairs": pairs, "moe_routed_layers": layers}
    slot_rows = sum((r for _, r in tallies[1:]), tallies[0][1])
    return {"prefill_moe_held_pairs": pairs, "prefill_moe_expert_rows": slot_rows}


def counts_held_pairs(moe, ids_shape, attend_to_cache: bool, layout) -> bool:
    """Whether a step program over the block layout tallies its routed layers'
    held pairs: the token-generation program of a share and, of its prefill
    programs, those whose expert layers take the compact form."""
    if moe is None or moe.held_experts is None or not isinstance(layout, BlockKVLayout):
        return False
    B, S = ids_shape
    if S == 1:
        return attend_to_cache
    return moe_ops.expert_form(moe, B * S) == "compact"


def causal_lm_forward(
    arch: DecoderArch,
    inv_freq: np.ndarray,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    *,
    attend_to_cache: bool,
    kv_window: Optional[int] = None,
    policy: ShardingPolicy = DEFAULT_POLICY,
    layout=DEFAULT_KV_LAYOUT,
    gather_last_token: bool = True,
    output_logits: bool = False,
    output_all_logits: bool = False,
    output_argmax_all: bool = False,
    output_logit_stats: bool = False,
    on_device_sampling: bool = True,
    do_sample: bool = False,
    global_topk: int = 256,
    deterministic: bool = False,
    dp_sampling: bool = False,
    return_next_inputs: bool = False,
    output_hidden: bool = False,
    aux_hidden_indices: Optional[Tuple[int, ...]] = None,
    image_token_id: Optional[int] = None,
    tensor_capture: Optional[Tuple[str, ...]] = None,
    tensor_replacement: Optional[Tuple[str, ...]] = None,
    mixed_rows: bool = False,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """One submodel forward (reference: model_base.py:713 NeuronBaseModel.forward).

    ``batch`` keys: input_ids (B,S) i32, position_ids (B,S) i32,
    last_token_index (B,) i32, sampling_params (B,3) f32, rng key.
    Returns (outputs, new_cache); outputs has "tokens" and/or "logits".

    ``mixed_rows`` (the serving engine's one-dispatch mixed step): the batch
    dim is 1 and the scheduler's ROWS live along the packed token axis,
    tagged by ``mixed_row_ids``; ``last_token_index`` is (R,) packed indices
    of each row's newest token and ``sampling_params`` is (R, 3), so the
    lm_head/sampling tail runs with R as its batch dim.
    """
    from nxdi_tpu.config import to_jax_dtype

    input_ids = batch["input_ids"]
    position_ids = batch["position_ids"]
    compute_dtype = to_jax_dtype(arch.dtype)

    with jax.named_scope("embed"):
        hidden = jnp.take(params["embed_tokens"], input_ids, axis=0).astype(compute_dtype)
        if arch.embed_scale is not None:
            # gemma scales embeddings by sqrt(hidden) AFTER the dtype downcast
            # (reference: modeling_gemma3.py:238-241)
            hidden = hidden * jnp.asarray(arch.embed_scale, compute_dtype)
        if arch.learned_pos_embeds:
            hidden = hidden + jnp.take(
                params["position_embeddings"], position_ids, axis=0
            ).astype(compute_dtype)
        if image_token_id is not None and "image_embeds" in batch:
            # multimodal prefill: replace image-placeholder token embeddings with
            # the projected vision features, row-local order (reference: the
            # image-to-text CTE merging vision embeds, image_to_text_model_base.py)
            img = batch["image_embeds"].astype(compute_dtype)  # (B, N, hidden)
            is_img = input_ids == image_token_id  # (B, S)
            idx = jnp.clip(jnp.cumsum(is_img, axis=1) - 1, 0, img.shape[1] - 1)
            gathered = jnp.take_along_axis(
                img, idx[:, :, None].astype(jnp.int32), axis=1
            )
            hidden = jnp.where(is_img[:, :, None], gathered, hidden)
        if "fc" in params:
            # EAGLE draft input: concat(token embedding, previous-position feature)
            # projected back to the hidden size (reference: the EAGLE draft fc,
            # modeling_llama.py:1408, fed target hidden states model_base.py:1581).
            feats = batch["prev_hidden"][:, : input_ids.shape[1]].astype(compute_dtype)
            hidden = _linear(
                jnp.concatenate([hidden, feats], axis=-1),
                params["fc"], arch.act_quant, arch.act_clamp,
            )
        if tensor_replacement and "embeds" in tensor_replacement:
            # tensor replacement (capture in reverse, reference:
            # utils/tensor_replacement/registry.py): swap the post-embedding
            # stream for the injected host tensor when its mask is set — one
            # compiled program serves both plain (zero mask) and replaced runs
            hidden = jnp.where(
                batch["tr_embeds_mask"][0] > 0,
                batch["tr_embeds"].astype(compute_dtype), hidden,
            )
        hidden = constrain(hidden, policy.hidden)
    inv_freq = np.asarray(inv_freq)
    if arch.mrope_section is not None and "mrope_position_ids" in batch:
        from nxdi_tpu.ops.rope import mrope_cos_sin

        cos, sin = mrope_cos_sin(
            batch["mrope_position_ids"][..., : input_ids.shape[1]],
            inv_freq, arch.mrope_section, dtype=jnp.float32,
            interleaved=arch.mrope_interleaved,
        )
    elif arch.longrope_original_max is not None and inv_freq.ndim == 2:
        # LongRoPE: [short, long] frequency sets, selected per forward from
        # the true max position (padding lanes continue the arange past the
        # real last token, so read positions at last_token_index). The regime
        # is a scalar, so select the frequency SET before the trig — one
        # cos/sin build instead of two.
        if "last_token_index" in batch:
            real_last = jnp.take_along_axis(
                position_ids, batch["last_token_index"][:, None], axis=1
            )
            seq_len_now = jnp.max(real_last) + 1
        else:
            seq_len_now = jnp.max(position_ids) + 1
        is_long = seq_len_now > arch.longrope_original_max
        inv = jnp.where(is_long, jnp.asarray(inv_freq[1]), jnp.asarray(inv_freq[0]))
        cos, sin = rope_cos_sin(position_ids, inv, dtype=jnp.float32)
    elif inv_freq.ndim == 2:  # (2, D/2): [global, local] thetas (gemma3)
        cos_g, sin_g = rope_cos_sin(position_ids, inv_freq[0], dtype=jnp.float32)
        cos_l, sin_l = rope_cos_sin(position_ids, inv_freq[1], dtype=jnp.float32)
        cos = jnp.stack([cos_g, cos_l])
        sin = jnp.stack([sin_g, sin_l])
    else:
        cos, sin = rope_cos_sin(position_ids, inv_freq, dtype=jnp.float32)
    if arch.rope_mscale != 1.0:
        cos = cos * arch.rope_mscale
        sin = sin * arch.rope_mscale

    if isinstance(layout, BlockKVLayout):
        slots = cache["k"].shape[1]
        cache_spec = BlockKVCacheSpec(
            num_layers=arch.num_layers,
            num_blocks=slots // layout.block_size,
            block_size=layout.block_size,
            num_kv_heads=arch.num_kv_heads,
            head_dim=arch.head_dim,
            dtype=arch.dtype,
        )
    else:
        cache_spec = arch.kv_cache_spec(cache["k"].shape[1], cache["k"].shape[3])
    cache_inputs = collect_cache_inputs(batch)
    if (
        arch.bidirectional_image_attention
        and image_token_id is not None
        and input_ids.shape[1] > 1
        and not attend_to_cache
    ):
        # per-image span ids (consecutive placeholder runs; distinct images
        # never attend each other — HF image_group_ids semantics), derived
        # in-graph so no extra host input is needed. PREFILL-stage programs
        # only (attend_to_cache=False): a cache-attending S>1 window is a
        # speculation verify pass whose generated tokens carry no image spans
        # — computing spans there tripped attention_block's prefix-caching
        # rejection at trace time and kept fused/EAGLE speculation from
        # compiling on gemma3-vision configs (ADVICE r5). Prefix-cached /
        # chunked prefill (also cache-attending S>1) is rejected up front at
        # wrapper construction for these models (runtime/model_wrapper.py).
        is_img = input_ids == image_token_id
        starts = is_img & ~jnp.concatenate(
            [jnp.zeros_like(is_img[:, :1]), is_img[:, :-1]], axis=1
        )
        cache_inputs["bidir_spans"] = jnp.where(
            is_img, jnp.cumsum(starts.astype(jnp.int32), axis=1), 0
        )
    layer_injections = None
    if image_token_id is not None and "deepstack_embeds" in batch:
        # qwen3-vl deepstack: layer k's output gains the k-th vision feature
        # stream at image-placeholder positions (reference: qwen3_vl
        # _deepstack_process; HF Qwen3VLTextModel layer loop)
        ds = batch["deepstack_embeds"].astype(compute_dtype)  # (B, K, N, H)
        K = ds.shape[1]
        is_img = input_ids == image_token_id  # (B, S)
        idx = jnp.clip(jnp.cumsum(is_img, axis=1) - 1, 0, ds.shape[2] - 1)
        gathered = jnp.take_along_axis(
            ds, idx[:, None, :, None].astype(jnp.int32), axis=2
        )  # (B, K, S, H)
        inj = jnp.where(is_img[:, None, :, None], gathered, 0.0)
        inj = jnp.swapaxes(inj, 0, 1)  # (K, B, S, H)
        pad = arch.num_layers - K
        layer_injections = jnp.concatenate(
            [inj, jnp.zeros((pad,) + inj.shape[1:], inj.dtype)], axis=0
        )

    spec_window_inputs = None
    if "k_spec" in cache:
        # fused-speculation draft window scratch (speculation/fused.py): the
        # window's absolute rope positions and this step's scratch column
        spec_window_inputs = (
            batch["spec_win_pos"].astype(jnp.int32),
            batch["spec_win_slot"].astype(jnp.int32),
        )

    layer_replacements = None
    if tensor_replacement and "layers" in tensor_replacement:
        layer_replacements = (
            jnp.swapaxes(batch["tr_layer_values"], 0, 1),  # (L, B, S, H)
            batch["tr_layer_mask"][0],  # (L,) — every batch row carries the same mask
        )

    captured: Dict[str, jax.Array] = {}
    if tensor_capture and "embeds" in tensor_capture:
        captured["embeds"] = hidden
    layer_hiddens = None
    held_tally = None
    if tensor_capture and "layer_hiddens" in tensor_capture and not aux_hidden_indices:
        aux_hidden_indices = ()  # falsy: don't emit aux_hidden output
        hidden, new_cache, layer_hiddens = run_decoder_layers(
            arch, params["layers"], hidden, cos, sin, cache,
            position_ids, cache_spec, attend_to_cache, kv_window=kv_window,
            policy=policy, layout=layout, cache_inputs=cache_inputs,
            collect_hidden=True, adapter_ids=batch.get("adapter_ids"),
            layer_injections=layer_injections,
            layer_replacements=layer_replacements,
            spec_window_inputs=spec_window_inputs,
        )
        captured["layer_hiddens"] = layer_hiddens
    elif aux_hidden_indices:
        hidden, new_cache, layer_hiddens = run_decoder_layers(
            arch, params["layers"], hidden, cos, sin, cache,
            position_ids, cache_spec, attend_to_cache, kv_window=kv_window,
            policy=policy, layout=layout, cache_inputs=cache_inputs,
            collect_hidden=True, adapter_ids=batch.get("adapter_ids"),
            layer_injections=layer_injections,
            layer_replacements=layer_replacements,
            spec_window_inputs=spec_window_inputs,
        )
        if tensor_capture and "layer_hiddens" in tensor_capture:
            captured["layer_hiddens"] = layer_hiddens
    else:
        if counts_held_pairs(arch.moe, input_ids.shape, attend_to_cache, layout):
            # one chip's share of an expert-parallel layer: count the (row,
            # expert) pairs that fell on held experts
            held_tally = []
        hidden, new_cache = run_decoder_layers(
            arch, params["layers"], hidden, cos, sin, cache,
            position_ids, cache_spec, attend_to_cache, kv_window=kv_window,
            policy=policy, layout=layout, cache_inputs=cache_inputs,
            adapter_ids=batch.get("adapter_ids"),
            layer_injections=layer_injections,
            layer_replacements=layer_replacements,
            spec_window_inputs=spec_window_inputs,
            moe_held_tally=held_tally,
        )
    if tensor_replacement and "hidden" in tensor_replacement:
        hidden = jnp.where(
            batch["tr_hidden_mask"][0] > 0,
            batch["tr_hidden"].astype(compute_dtype), hidden,
        )
        hidden = constrain(hidden, policy.hidden)
    pre_norm_hidden = hidden
    with jax.named_scope("final_norm"):
        if "norm" in params:  # EAGLE drafts have no final norm
            hidden = _norm(arch, hidden, params["norm"])

    with jax.named_scope("lm_head"):
        lm_head = params.get("lm_head")
        if lm_head is None:  # tied embeddings
            lm_head = jnp.swapaxes(params["embed_tokens"], 0, 1)

        if mixed_rows:
            # packed mixed stream: gather each ROW's newest token off the single
            # packed batch row — everything below (lm_head, stats, sampling)
            # sees (R, 1, hidden) exactly like an R-row decode batch
            idx = batch["last_token_index"].astype(jnp.int32)  # (R,)
            hidden = jnp.take(hidden[0], idx, axis=0)[:, None, :]
        elif gather_last_token and not output_all_logits:
            idx = batch["last_token_index"][:, None, None]  # (B,1,1)
            hidden = jnp.take_along_axis(
                hidden, jnp.broadcast_to(idx, (hidden.shape[0], 1, hidden.shape[2])), axis=1
            )  # (B, 1, hidden)

        logits = (hidden @ lm_head.astype(hidden.dtype)).astype(jnp.float32)
        if "lm_head_bias" in params:  # phi lineage: biased lm_head
            logits = logits + params["lm_head_bias"].astype(jnp.float32)
        if arch.logits_scaling != 1.0:
            logits = logits / arch.logits_scaling
        if arch.final_logit_softcap is not None:
            cap = arch.final_logit_softcap
            logits = cap * jnp.tanh(logits / cap)
        logits = constrain(logits, policy.logits)
        logits = sampling_ops.mask_padded_logits(logits, arch.vocab_pad)

    outputs: Dict[str, jax.Array] = {}
    if held_tally:
        # two scalars beside the tokens, in the same fetch (serving/engine.py
        # _collect_decode -> StepRecord.moe_held_pairs, _prefill_chunk ->
        # .prefill_moe_held_pairs); batch-padding rows are rows the expert
        # layer computed, and are in the count; a prefill's bucket padding has
        # no slot in the compact form, and is not
        outputs.update(held_pair_outputs(held_tally, prefill=input_ids.shape[1] > 1))
    if tensor_capture:
        if "hidden" in tensor_capture:
            captured["hidden"] = pre_norm_hidden
        if "logits" in tensor_capture:
            captured["logits"] = logits
        outputs["captured"] = captured
    if output_hidden:
        # last-layer hidden BEFORE the final norm — the EAGLE feature stream
        outputs["hidden"] = pre_norm_hidden
    if aux_hidden_indices:
        # (B, S, len(indices)*H) concat of selected layers' outputs (EAGLE3)
        sel = [layer_hiddens[i] for i in aux_hidden_indices]
        outputs["aux_hidden"] = jnp.concatenate(sel, axis=-1)
    if output_all_logits and gather_last_token:
        # still provide the last-position logits for the sampler
        idx = batch["last_token_index"][:, None, None]
        last_logits = jnp.take_along_axis(
            logits, jnp.broadcast_to(idx, (logits.shape[0], 1, logits.shape[2])), axis=1
        )
    else:
        last_logits = logits

    if output_logit_stats:
        # numerics sentinel (TpuConfig(sentinel=...)): a (B, 5) health
        # readout over the sampled position's logit row block, computed
        # in-graph so only five floats per row cross the program boundary
        outputs["logit_stats"] = sampling_ops.logit_health_stats(last_logits)
    if output_argmax_all:
        # speculation verify: the greedy token at EVERY position, selected
        # in-graph — the full-vocab fp32 logits never cross the program
        # boundary, the accept/gather logic downstream runs on (B, S) tokens
        outputs["tokens"] = sampling_ops.greedy_sample(logits)
    with jax.named_scope("sample"):
        if on_device_sampling:
            sample_in = last_logits[:, -1, :]
            if dp_sampling:
                # DataParallelSampler analog (reference: sampling.py:469-569):
                # batch rows shard over the tp world for the top-k stages; GSPMD
                # gathers the sampled tokens
                sample_in = constrain(sample_in, P(AXIS_MP, None))
            tokens = sampling_ops.sample(
                sample_in,
                batch["sampling_params"],
                rng=batch.get("rng"),
                do_sample=do_sample,
                global_topk=global_topk,
                deterministic=deterministic,
            )
            outputs["tokens"] = tokens[:, None]  # (B, 1)
    if output_logits or output_all_logits or (
        not on_device_sampling and not output_argmax_all
    ):
        outputs["logits"] = logits[..., : arch.vocab_size - arch.vocab_pad]

    if return_next_inputs and on_device_sampling:
        # Device-resident generation loop (the analog of the reference's async
        # execution + ranked I/O keeping tensors on device between steps,
        # async_execution.py:131, model_wrapper.py:623): emit the NEXT step's
        # token-generation inputs so the host never touches the hot path.
        nxt: Dict[str, jax.Array] = {
            "input_ids": outputs["tokens"].astype(jnp.int32),
            # next token goes one past each sequence's current last position
            "position_ids": (
                jnp.take_along_axis(
                    position_ids, batch["last_token_index"][:, None], axis=1
                )
                + 1
            ).astype(jnp.int32),
            "last_token_index": jnp.zeros_like(batch["last_token_index"]),
            "sampling_params": batch["sampling_params"],
        }
        if "rng" in batch:
            nxt["rng"] = sampling_ops.next_step_rng(batch["rng"])
        outputs["next_inputs"] = nxt
    return outputs, new_cache


# ---------------------------------------------------------------------------
# Multi-step decode: K token-generation steps in ONE compiled program
# ---------------------------------------------------------------------------

# step-batch keys chained from one in-scan decode step to the next (exactly
# the 1-step program's next_inputs contract)
_MULTISTEP_CHAIN_KEYS = (
    "input_ids", "position_ids", "last_token_index", "sampling_params",
)
# batch keys carried through the scan (and the window-to-window next_inputs)
# unchanged
_MULTISTEP_PASSTHROUGH_KEYS = ("seq_ids", "eos_token_ids", "pad_token_id")


def multi_step_token_gen(
    arch: DecoderArch,
    inv_freq: np.ndarray,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    *,
    num_steps: int,
    kv_window: Optional[int] = None,
    policy: ShardingPolicy = DEFAULT_POLICY,
    layout=DEFAULT_KV_LAYOUT,
    do_sample: bool = False,
    global_topk: int = 256,
    deterministic: bool = False,
    dp_sampling: bool = False,
    return_next_inputs: bool = True,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """K decode steps fused into one dispatch (the ``tkg_multistep`` submodel).

    One ``lax.scan`` chains K single-token ``causal_lm_forward`` steps —
    sample -> embed -> layer stack -> deferred KV commit -> position advance —
    entirely on device, so the host dispatches (and XLA enters/exits a
    program) once per K tokens instead of once per token. The per-step
    plumbing is EXACTLY the 1-step program's ``next_inputs`` contract,
    including the :func:`sampling.next_step_rng` key schedule, which makes
    the K-step scan token-identical to K chained 1-step dispatches (greedy
    and sampled).

    ``batch`` extends the decode contract with three optional fixed-shape
    inputs for in-scan EOS/budget handling:
      - ``eos_token_ids`` (B, E) int32, -1 = unused slot: once a row samples
        any of its EOS ids, its later in-window tokens are emitted as
        ``pad_token_id`` and the pad is what feeds the next step — the same
        stream the host-side sync loop produces for finished rows.
      - ``pad_token_id`` (B,) int32.
      - ``budget_steps`` (B,) int32, <= 0 = unlimited: row i may emit at most
        ``budget_steps[i]`` tokens this window, then finishes like EOS. This
        is what lets the serving engine dispatch a window LARGER than the
        smallest per-row remaining budget — near-EOS rows ride along and
        halt per-row instead of degrading the whole batch to 1-step.

    Finished rows (EOS'd or out of budget) freeze: their position stops
    advancing and their KV writes are dropped (negative write positions →
    the layout scatter's drop mode), so a long window can never push a
    finished row's pad-chain garbage over its own last real KV line or out
    of the compiled window.

    Returns outputs with ``tokens`` (B, K) — all K emitted tokens, in order —
    and (optionally) ``next_inputs`` carrying the step-batch for the NEXT
    window plus the passthrough inputs, so windows chain device-resident.
    """
    B = batch["input_ids"].shape[0]
    eos_ids = batch.get("eos_token_ids")  # (B, E) int32; None = no masking
    pad_id = batch.get("pad_token_id")  # (B,) int32
    budget = batch.get("budget_steps")  # (B,) int32; None/<=0 = unlimited
    passthrough = {
        k: batch[k] for k in _MULTISTEP_PASSTHROUGH_KEYS if k in batch
    }

    step0 = {k: batch[k] for k in _MULTISTEP_CHAIN_KEYS}
    if "rng" in batch:
        step0["rng"] = batch["rng"]

    def step(carry, t):
        sbatch, done, kvc = carry
        fwd_batch = dict(passthrough)
        fwd_batch.update(sbatch)
        fwd_batch["write_positions"] = jnp.where(
            done[:, None], jnp.int32(-1), sbatch["position_ids"]
        )
        out, kvc = causal_lm_forward(
            arch,
            inv_freq,
            params,
            kvc,
            fwd_batch,
            attend_to_cache=True,
            kv_window=kv_window,
            policy=policy,
            layout=layout,
            gather_last_token=False,
            on_device_sampling=True,
            do_sample=do_sample,
            global_topk=global_topk,
            deterministic=deterministic,
            dp_sampling=dp_sampling,
            return_next_inputs=True,
        )
        nxt = out["next_inputs"]
        tok = out["tokens"][:, 0]  # (B,)
        if eos_ids is not None:
            # finished rows emit (and feed forward) the pad token; a row
            # finishes the step AFTER its EOS is emitted, so the EOS itself
            # always lands in the output — the sync host loop's semantics
            pad = (
                pad_id.astype(tok.dtype)
                if pad_id is not None
                else jnp.zeros_like(tok)
            )
            emitted = jnp.where(done, pad, tok)
            done = done | jnp.any(emitted[:, None] == eos_ids, axis=1)
        else:
            emitted = tok
        if budget is not None:
            # the budget-hit token itself is real (the host's "length"
            # finish emits it); only LATER steps are frozen out
            done = done | ((budget > 0) & (t + 1 >= budget))
        new_sbatch = {
            "input_ids": emitted[:, None].astype(jnp.int32),
            "position_ids": jnp.where(
                done[:, None], sbatch["position_ids"], nxt["position_ids"]
            ),
            "last_token_index": nxt["last_token_index"],
            "sampling_params": nxt["sampling_params"],
        }
        if "rng" in sbatch:
            new_sbatch["rng"] = nxt["rng"]
        return (new_sbatch, done, kvc), emitted

    done0 = jnp.zeros((B,), bool)
    (step_k, _, cache), toks = jax.lax.scan(
        step, (step0, done0, cache), jnp.arange(num_steps, dtype=jnp.int32)
    )
    outputs: Dict[str, jax.Array] = {"tokens": jnp.swapaxes(toks, 0, 1)}  # (B, K)
    if return_next_inputs:
        nxt = dict(step_k)
        nxt.update(passthrough)
        outputs["next_inputs"] = nxt
    return outputs, cache


# ---------------------------------------------------------------------------
# Device-resident decode loop: while-loop with per-row EOS/budget exit
# ---------------------------------------------------------------------------


def device_loop_token_gen(
    arch: DecoderArch,
    inv_freq: np.ndarray,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    *,
    max_steps: int,
    kv_window: Optional[int] = None,
    policy: ShardingPolicy = DEFAULT_POLICY,
    layout=DEFAULT_KV_LAYOUT,
    do_sample: bool = False,
    global_topk: int = 256,
    deterministic: bool = False,
    dp_sampling: bool = False,
    outfeed: Optional[Any] = None,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """The ``tkg_device_loop`` submodel: a ``lax.while_loop`` whose body is
    one full sample -> embed -> layer stack -> KV-commit decode step, exiting
    as soon as EVERY row has sampled one of its EOS ids or exhausted its
    per-row token budget. Unlike the fixed-rung scan (``tkg_multistep``) the
    iteration count is data-dependent: a batch with heterogeneous remaining
    budgets runs ONE dispatch and each row halts exactly where the host loop
    would have stopped it — the host never re-enters the hot path to referee.

    Contract (all of ``multi_step_token_gen``'s, plus):
      - ``max_steps`` is the STATIC capacity of the token out-buffer
        (B, max_steps); the loop exits early once all rows are done, so the
        cap bounds — never schedules — the work.
      - ``budget_steps`` (B,) int32, <= 0 = unlimited: per-row emission
        budget; the budget-hit token itself is emitted (the host's "length"
        finish semantics).
      - sampling keys are COUNTER-BASED: iteration t draws with
        ``batch["rng"] + [0, t]`` — i.e. the host ``StepRngSchedule``'s own
        ``(seed, counter + t)`` sequence — so a fixed-seed sampled loop
        reproduces N chained 1-step engine dispatches token-for-token (the
        host advances its counter by the returned ``loop_iters - 1``).
      - ``outfeed``, when given, is a host callable ``(t, tokens, done)``
        invoked per iteration via an unordered ``io_callback`` — the
        device→host token out-feed ring. The (B, max_steps) result buffer is
        ALWAYS returned too, so CPU/interpret runs (and tier-1) stay exact
        without the ring.

    Finished rows freeze exactly like the scan: pad-token feed-forward,
    position pinned, KV writes dropped via negative write positions.

    Returns outputs with ``tokens`` (B, max_steps) — entries past a row's
    halt point are ``pad_token_id`` — and ``loop_iters`` (scalar int32), the
    number of body iterations the loop actually ran.
    """
    from jax.experimental import io_callback

    B = batch["input_ids"].shape[0]
    eos_ids = batch.get("eos_token_ids")
    pad_id = batch.get("pad_token_id")
    budget = batch.get("budget_steps")
    base_rng = batch.get("rng")
    passthrough = {
        k: batch[k] for k in _MULTISTEP_PASSTHROUGH_KEYS if k in batch
    }

    step0 = {k: batch[k] for k in _MULTISTEP_CHAIN_KEYS}
    pad0 = (
        pad_id.astype(jnp.int32)
        if pad_id is not None
        else jnp.zeros((B,), jnp.int32)
    )
    toks0 = jnp.broadcast_to(pad0[:, None], (B, max_steps)).astype(jnp.int32)

    def cond(carry):
        t, done, _sbatch, _toks, _kvc = carry
        return (t < max_steps) & ~jnp.all(done)

    def body(carry):
        t, done, sbatch, toks, kvc = carry
        fwd_batch = dict(passthrough)
        fwd_batch.update(sbatch)
        fwd_batch["write_positions"] = jnp.where(
            done[:, None], jnp.int32(-1), sbatch["position_ids"]
        )
        if base_rng is not None:
            # counter-based key schedule: one host counter per iteration
            fwd_batch["rng"] = base_rng + jnp.array(
                [0, 1], jnp.uint32
            ) * t.astype(jnp.uint32)
        out, kvc = causal_lm_forward(
            arch,
            inv_freq,
            params,
            kvc,
            fwd_batch,
            attend_to_cache=True,
            kv_window=kv_window,
            policy=policy,
            layout=layout,
            gather_last_token=False,
            on_device_sampling=True,
            do_sample=do_sample,
            global_topk=global_topk,
            deterministic=deterministic,
            dp_sampling=dp_sampling,
            return_next_inputs=True,
        )
        nxt = out["next_inputs"]
        tok = out["tokens"][:, 0]  # (B,)
        emitted = jnp.where(done, pad0.astype(tok.dtype), tok)
        if eos_ids is not None:
            done = done | jnp.any(emitted[:, None] == eos_ids, axis=1)
        if budget is not None:
            done = done | ((budget > 0) & (t + 1 >= budget))
        toks = jax.lax.dynamic_update_slice(
            toks, emitted[:, None].astype(jnp.int32), (0, t)
        )
        if outfeed is not None:
            # unordered: iteration index t rides along so the host ring can
            # reassemble order without serializing the loop on the callback
            io_callback(outfeed, None, t, emitted, done, ordered=False)
        new_sbatch = {
            "input_ids": emitted[:, None].astype(jnp.int32),
            "position_ids": jnp.where(
                done[:, None], sbatch["position_ids"], nxt["position_ids"]
            ),
            "last_token_index": nxt["last_token_index"],
            "sampling_params": nxt["sampling_params"],
        }
        return (t + 1, done, new_sbatch, toks, kvc)

    done0 = jnp.zeros((B,), bool)
    t_end, _done, _sbatch, toks, cache = jax.lax.while_loop(
        cond, body, (jnp.int32(0), done0, step0, toks0, cache)
    )
    return {"tokens": toks, "loop_iters": t_end}, cache
