"""Qwen3Next family — hybrid linear-attention (GatedDeltaNet) + full attention.

Reference: models/qwen3_next/modeling_qwen3_next.py (1205 LoC):
``NeuronQwen3NextGatedDeltaNet`` linear attention with causal conv1d
(:347-620) interleaved with gated full-attention layers (:281).

TPU-native mapping:
  - the stack is HETEROGENEOUS (most layers are linear attention, every Nth is
    full attention, MLPs may be sparse MoE or dense) so the forward unrolls
    layers in Python instead of the homogeneous ``lax.scan`` the dense
    families use — compile time grows with depth, runtime does not;
  - the gated delta rule runs as a ``lax.scan`` over the sequence in fp32
    (prefill); decode advances the recurrent state one token per dispatch;
  - state lives in the cache pytree: per-full-layer KV slabs plus per-linear-
    layer causal-conv windows (last k inputs) and delta-rule states
    (B, Hv, dk, dv);
  - CTE right-padding is masked out of the state updates (decay frozen, beta
    zeroed, conv window gathered at the true last token) so bucket padding
    never pollutes the recurrence.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nxdi_tpu.config import InferenceConfig
from nxdi_tpu.models import dense
from nxdi_tpu.ops import attention as attn_ops
from nxdi_tpu.ops import sampling as sampling_ops
from nxdi_tpu.ops.norms import rms_norm
from nxdi_tpu.ops.rope import default_inv_freq, rope_cos_sin, rotate_half


@dataclass(frozen=True)
class Qwen3NextArch:
    num_layers: int
    hidden_size: int
    intermediate_size: int
    vocab_size: int
    vocab_pad: int
    rms_norm_eps: float
    layer_types: Tuple[str, ...]  # "linear_attention" | "full_attention"
    # full attention
    num_attention_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    # linear attention (GatedDeltaNet)
    num_v_heads: int
    num_k_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int
    # MoE (None -> dense MLP)
    num_experts: int = 0
    top_k: int = 0
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    @property
    def key_dim(self) -> int:
        return self.head_k_dim * self.num_k_heads

    @property
    def value_dim(self) -> int:
        return self.head_v_dim * self.num_v_heads

    @property
    def conv_dim(self) -> int:
        return self.key_dim * 2 + self.value_dim

    @property
    def n_full(self) -> int:
        return sum(t == "full_attention" for t in self.layer_types)

    @property
    def n_linear(self) -> int:
        return sum(t == "linear_attention" for t in self.layer_types)


class Qwen3NextInferenceConfig(dense.DenseInferenceConfig):
    REQUIRED = dense.DenseInferenceConfig.REQUIRED + [
        "linear_num_value_heads",
        "linear_num_key_heads",
        "linear_key_head_dim",
        "linear_value_head_dim",
        "linear_conv_kernel_dim",
    ]

    def add_derived_config(self):
        super().add_derived_config()
        defaults = {
            "partial_rotary_factor": 0.25,
            "layer_types": None,
            "num_experts": 0,
            "num_experts_per_tok": 0,
            "moe_intermediate_size": 0,
            "shared_expert_intermediate_size": 0,
            "norm_topk_prob": True,
            "decoder_sparse_step": 1,
            "mlp_only_layers": [],
            "head_dim": self.hidden_size // self.num_attention_heads,
        }
        for k, v in defaults.items():
            if not hasattr(self, k) or getattr(self, k) is None:
                setattr(self, k, v)


def _layer_types(config: InferenceConfig) -> Tuple[str, ...]:
    lt = getattr(config, "layer_types", None)
    if lt:
        return tuple(lt)
    # HF default pattern: every 4th layer full attention
    return tuple(
        "full_attention" if (i + 1) % 4 == 0 else "linear_attention"
        for i in range(config.num_hidden_layers)
    )


def _uses_moe(config: InferenceConfig, i: int) -> bool:
    return (
        config.num_experts > 0
        and i not in (config.mlp_only_layers or [])
        and (i + 1) % (config.decoder_sparse_step or 1) == 0
    )


def build_arch(config: InferenceConfig, **overrides) -> Qwen3NextArch:
    types = _layer_types(config)
    moe_layers = [_uses_moe(config, i) for i in range(config.num_hidden_layers)]
    if any(moe_layers) and not all(moe_layers):
        raise NotImplementedError(
            "qwen3_next with MIXED dense/MoE MLP layers is not supported yet"
        )
    from nxdi_tpu.config import dtype_name

    vocab, vocab_pad = dense.padded_vocab(config)
    kwargs = dict(
        num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size,
        intermediate_size=config.intermediate_size,
        vocab_size=vocab,
        vocab_pad=vocab_pad,
        rms_norm_eps=config.rms_norm_eps,
        layer_types=types,
        num_attention_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        rotary_dim=int(config.head_dim * config.partial_rotary_factor),
        num_v_heads=config.linear_num_value_heads,
        num_k_heads=config.linear_num_key_heads,
        head_k_dim=config.linear_key_head_dim,
        head_v_dim=config.linear_value_head_dim,
        conv_kernel=config.linear_conv_kernel_dim,
        num_experts=config.num_experts if any(moe_layers) else 0,
        top_k=config.num_experts_per_tok,
        moe_intermediate_size=config.moe_intermediate_size,
        shared_expert_intermediate_size=config.shared_expert_intermediate_size,
        norm_topk_prob=bool(config.norm_topk_prob),
        tie_word_embeddings=getattr(config, "tie_word_embeddings", False),
        dtype=dtype_name(config.tpu_config.dtype),
    )
    kwargs.update(overrides)
    return Qwen3NextArch(**kwargs)


def build_inv_freq(config: InferenceConfig) -> np.ndarray:
    rotary_dim = int(config.head_dim * config.partial_rotary_factor)
    return default_inv_freq(rotary_dim, getattr(config, "rope_theta", 10000.0))


def _g_norm(arch, x, w):
    """(1+w) float32 rms norm (Qwen3NextRMSNorm)."""
    return rms_norm(x, w, arch.rms_norm_eps, gemma_style=True)


# ---------------------------------------------------------------------------
# Linear attention (GatedDeltaNet)
# ---------------------------------------------------------------------------

def _l2norm(x, eps=1e-6):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def _split_qkvz_ba(arch: Qwen3NextArch, qkvz, ba):
    """HF's interleaved per-k-head ordering (fix_query_key_value_ordering)."""
    B, S = qkvz.shape[:2]
    gk, gv = arch.num_k_heads, arch.num_v_heads
    r = gv // gk
    dk, dv = arch.head_k_dim, arch.head_v_dim
    qkvz = qkvz.reshape(B, S, gk, 2 * dk + 2 * r * dv)
    q = qkvz[..., :dk]
    k = qkvz[..., dk : 2 * dk]
    v = qkvz[..., 2 * dk : 2 * dk + r * dv].reshape(B, S, gv, dv)
    z = qkvz[..., 2 * dk + r * dv :].reshape(B, S, gv, dv)
    ba = ba.reshape(B, S, gk, 2 * r)
    b = ba[..., :r].reshape(B, S, gv)
    a = ba[..., r:].reshape(B, S, gv)
    return q, k, v, z, b, a


def _delta_rule_scan(q, k, v, g, beta, state0):
    """Gated delta rule over the sequence (fp32; HF
    torch_recurrent_gated_delta_rule semantics with in-kernel qk l2 norm).

    q/k: (B, S, Hv, dk); v: (B, S, Hv, dv); g/beta: (B, S, Hv);
    state0: (B, Hv, dk, dv). Returns (out (B, S, Hv, dv), final state).
    """
    q = _l2norm(q) * (q.shape[-1] ** -0.5)
    k = _l2norm(k)
    v = v.astype(jnp.float32)
    g = g.astype(jnp.float32)
    beta = beta.astype(jnp.float32)

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (B, Hv, d*) / (B, Hv)
        decay = jnp.exp(g_t)[..., None, None]
        state = state * decay
        kv_mem = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        delta = (v_t - kv_mem) * b_t[..., None]
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, delta)
        out_t = jnp.einsum("bhkv,bhk->bhv", state, q_t)
        return state, out_t

    xs = tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
    state, outs = jax.lax.scan(step, state0.astype(jnp.float32), xs)
    return jnp.swapaxes(outs, 0, 1), state


def linear_attention_layer(
    arch: Qwen3NextArch,
    lp: Dict[str, Any],
    hidden,  # (B, S, H) already input-normed
    conv_state,  # (B, conv_dim, kernel)
    rec_state,  # (B, Hv, dk, dv) fp32
    valid,  # (B, S) bool — False on padded positions
    is_decode: bool,
):
    B, S, _ = hidden.shape
    dt = hidden.dtype
    qkvz = hidden @ lp["in_proj_qkvz"]
    ba = hidden @ lp["in_proj_ba"]
    q, k, v, z, b, a = _split_qkvz_ba(arch, qkvz, ba)

    mixed = jnp.concatenate(
        [q.reshape(B, S, -1), k.reshape(B, S, -1), v.reshape(B, S, -1)], axis=-1
    )  # (B, S, conv_dim)
    mixed = jnp.where(valid[..., None], mixed, 0.0)
    x_ch = jnp.swapaxes(mixed, 1, 2)  # (B, conv_dim, S)
    K = arch.conv_kernel
    w = lp["conv1d"]  # (conv_dim, K)

    if is_decode:
        # shift the window, append the current input, depthwise dot (HF
        # causal_conv1d_update)
        conv_state = jnp.concatenate([conv_state[:, :, 1:], x_ch], axis=-1)
        conv_out = jnp.sum(conv_state * w[None], axis=-1, keepdims=True)  # (B,C,1)
        new_conv = conv_state
    else:
        padded = jnp.pad(x_ch, ((0, 0), (0, 0), (K - 1, 0)))
        conv_out = jax.lax.conv_general_dilated(
            padded.astype(jnp.float32),
            w[:, None, :].astype(jnp.float32),
            (1,),
            [(0, 0)],
            dimension_numbers=("NCW", "OIW", "NCW"),
            feature_group_count=arch.conv_dim,
        ).astype(dt)
        # conv window = last K REAL inputs per row (gathered at the true end;
        # bucket padding beyond last_token_index must not enter the state)
        lti = jnp.sum(valid.astype(jnp.int32), axis=1) - 1  # (B,)
        idx = lti[:, None] - (K - 1) + jnp.arange(K, dtype=jnp.int32)[None, :]
        take = jnp.clip(idx, 0, S - 1)
        gathered = jnp.take_along_axis(
            x_ch, jnp.broadcast_to(take[:, None, :], (B, arch.conv_dim, K)), axis=2
        )
        new_conv = jnp.where((idx >= 0)[:, None, :], gathered, 0.0).astype(conv_state.dtype)

    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(dt)
    mixed = jnp.swapaxes(conv_out, 1, 2)  # (B, S, conv_dim)
    kd, vd = arch.key_dim, arch.value_dim
    q = mixed[..., :kd].reshape(B, S, arch.num_k_heads, arch.head_k_dim)
    k = mixed[..., kd : 2 * kd].reshape(B, S, arch.num_k_heads, arch.head_k_dim)
    v = mixed[..., 2 * kd :].reshape(B, S, arch.num_v_heads, arch.head_v_dim)

    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)
    )
    # freeze the recurrence on padded positions: no decay, no write
    g = jnp.where(valid[..., None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)

    r = arch.num_v_heads // arch.num_k_heads
    if r > 1:
        q = jnp.repeat(q, r, axis=2)
        k = jnp.repeat(k, r, axis=2)

    core, new_rec = _delta_rule_scan(q, k, v, g, beta, rec_state)
    core = core.astype(dt)

    # gated per-head rms norm then silu(z) gate (Qwen3NextRMSNormGated)
    cf = core.astype(jnp.float32)
    var = jnp.mean(cf * cf, axis=-1, keepdims=True)
    normed = (cf * jax.lax.rsqrt(var + arch.rms_norm_eps)).astype(dt) * lp["norm"]
    out = (normed.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
    out = out.reshape(B, S, arch.value_dim) @ lp["out_proj"]
    return out, new_conv, new_rec


# ---------------------------------------------------------------------------
# Full attention (gated, partial rotary)
# ---------------------------------------------------------------------------

def full_attention_layer(
    arch: Qwen3NextArch,
    lp: Dict[str, Any],
    hidden,
    cos,
    sin,
    k_cache,  # (B, KV, W, D)
    v_cache,
    position_ids,
    attend_to_cache: bool,
    kv_window: Optional[int],
):
    B, S, _ = hidden.shape
    H, KV, D = arch.num_attention_heads, arch.num_kv_heads, arch.head_dim

    qg = (hidden @ lp["q_proj"]).reshape(B, S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:].reshape(B, S, H * D)
    k = (hidden @ lp["k_proj"]).reshape(B, S, KV, D)
    v = (hidden @ lp["v_proj"]).reshape(B, S, KV, D)
    q = _g_norm(arch, q, lp["q_norm"])
    k = _g_norm(arch, k, lp["k_norm"])

    q = jnp.swapaxes(q, 1, 2)
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)

    # partial rotary: rope the first rotary_dim dims only
    rd = arch.rotary_dim
    cosb = cos[:, None].astype(jnp.float32)
    sinb = sin[:, None].astype(jnp.float32)

    def rope(x):
        xr = x[..., :rd].astype(jnp.float32)
        out = xr * cosb + rotate_half(xr) * sinb
        return jnp.concatenate([out.astype(x.dtype), x[..., rd:]], axis=-1)

    q, k = rope(q), rope(k)

    # exact-position KV write
    pos = position_ids
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    new_k = k_cache.at[b_idx, :, pos].set(jnp.swapaxes(k, 1, 2).astype(k_cache.dtype), mode="drop")
    new_v = v_cache.at[b_idx, :, pos].set(jnp.swapaxes(v, 1, 2).astype(v_cache.dtype), mode="drop")

    if attend_to_cache:
        W = kv_window if kv_window is not None else new_k.shape[2]
        kk = new_k[:, :, :W].astype(q.dtype)
        vv = new_v[:, :, :W].astype(q.dtype)
        kv_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (B, W))
        ctx = attn_ops.attention_with_positions(q, kk, vv, position_ids, kv_pos)
    else:
        ctx = attn_ops.attention_with_positions(q, k, v, position_ids, position_ids)

    ctx = jnp.swapaxes(ctx, 1, 2).reshape(B, S, H * D)
    ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
    return ctx @ lp["o_proj"], new_k, new_v


def _mlp(arch: Qwen3NextArch, lp, x):
    gate = jax.nn.silu(x @ lp["gate_proj"])
    return (gate * (x @ lp["up_proj"])) @ lp["down_proj"]


def _moe_arch(arch: Qwen3NextArch):
    from nxdi_tpu.ops.moe import MoEArch

    return MoEArch(
        num_experts=arch.num_experts,
        top_k=arch.top_k,
        intermediate_size=arch.moe_intermediate_size,
        hidden_act="silu",
        norm_topk_prob=arch.norm_topk_prob,
        shared_expert_intermediate_size=arch.shared_expert_intermediate_size,
        shared_expert_gated=True,
    )


def _moe(arch: Qwen3NextArch, lp, x):
    # the qwen-style router (softmax -> top-k -> renorm) + sigmoid-gated
    # shared expert IS the shared MoE machinery — reuse it (ops/moe.py)
    from nxdi_tpu.ops.moe import moe_block

    return moe_block(arch, _moe_arch(arch), lp, x)


# ---------------------------------------------------------------------------
# Forward (ModelWrapper contract)
# ---------------------------------------------------------------------------

def qwen3next_forward(
    arch: Qwen3NextArch,
    inv_freq: np.ndarray,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    *,
    attend_to_cache: bool,
    kv_window: Optional[int] = None,
    policy=None,
    layout=None,
    gather_last_token: bool = True,
    output_logits: bool = False,
    output_all_logits: bool = False,
    on_device_sampling: bool = True,
    do_sample: bool = False,
    global_topk: int = 256,
    deterministic: bool = False,
    return_next_inputs: bool = False,
    **_unused,
):
    from nxdi_tpu.config import to_jax_dtype

    input_ids = batch["input_ids"]
    position_ids = batch["position_ids"]
    dt = to_jax_dtype(arch.dtype)
    B, S = input_ids.shape

    hidden = jnp.take(params["embed_tokens"], input_ids, axis=0).astype(dt)
    cos, sin = rope_cos_sin(position_ids, inv_freq, dtype=jnp.float32)

    if attend_to_cache:
        valid = jnp.ones((B, S), bool)  # decode: every position is real
    else:
        lti = batch["last_token_index"]
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] <= lti[:, None]

    from nxdi_tpu.models.state_routing import put_rows, take_rows

    sids = batch.get("seq_ids")  # continuous batching: row i -> cache line
    new_k, new_v = cache["k"], cache["v"]
    new_conv, new_rec = cache["conv"], cache["rec"]
    fi = li = 0
    for i, lt in enumerate(arch.layer_types):
        lp = params["layers"][i]
        h = _g_norm(arch, hidden, lp["input_layernorm"])
        if lt == "linear_attention":
            out, c_new, r_new = linear_attention_layer(
                arch, lp["linear_attn"], h,
                take_rows(new_conv[li], sids), take_rows(new_rec[li], sids),
                valid, is_decode=attend_to_cache,
            )
            new_conv = put_rows(new_conv, li, c_new, sids)
            new_rec = put_rows(new_rec, li, r_new, sids)
            li += 1
        else:
            out, k_new, v_new = full_attention_layer(
                arch, lp["self_attn"], h, cos, sin,
                take_rows(new_k[fi], sids), take_rows(new_v[fi], sids),
                position_ids, attend_to_cache, kv_window,
            )
            new_k = put_rows(new_k, fi, k_new, sids)
            new_v = put_rows(new_v, fi, v_new, sids)
            fi += 1
        hidden = hidden + out
        h = _g_norm(arch, hidden, lp["post_attention_layernorm"])
        if arch.num_experts:
            hidden = hidden + _moe(arch, lp["mlp"], h)
        else:
            hidden = hidden + _mlp(arch, lp["mlp"], h)

    hidden = _g_norm(arch, hidden, params["norm"])
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = jnp.swapaxes(params["embed_tokens"], 0, 1)
    if gather_last_token and not output_all_logits:
        idx = batch["last_token_index"][:, None, None]
        hidden = jnp.take_along_axis(
            hidden, jnp.broadcast_to(idx, (B, 1, hidden.shape[2])), axis=1
        )
    logits = (hidden @ lm_head.astype(hidden.dtype)).astype(jnp.float32)
    logits = sampling_ops.mask_padded_logits(logits, arch.vocab_pad)

    outputs: Dict[str, jax.Array] = {}
    if on_device_sampling:
        tokens = sampling_ops.sample(
            logits[:, -1, :],
            batch["sampling_params"],
            rng=batch.get("rng"),
            do_sample=do_sample,
            global_topk=global_topk,
            deterministic=deterministic,
        )
        outputs["tokens"] = tokens[:, None]
    if output_logits or output_all_logits or not on_device_sampling:
        outputs["logits"] = logits[..., : arch.vocab_size - arch.vocab_pad]
    new_cache = {"k": new_k, "v": new_v, "conv": new_conv, "rec": new_rec}
    return outputs, new_cache


# ---------------------------------------------------------------------------
# Conversion / specs / struct
# ---------------------------------------------------------------------------

def convert_hf_state_dict(
    state_dict: Dict[str, np.ndarray], config: InferenceConfig
) -> Dict[str, Any]:
    arch = build_arch(config)
    dt = dense.np_dtype(arch.dtype)

    def get(name):
        for k in (name, f"model.{name}"):
            if k in state_dict:
                return np.asarray(state_dict[k], dtype=dt)
        raise KeyError(name)

    layers = []
    for i, lt in enumerate(arch.layer_types):
        pre = f"layers.{i}."
        lp: Dict[str, Any] = {
            "input_layernorm": get(pre + "input_layernorm.weight"),
            "post_attention_layernorm": get(pre + "post_attention_layernorm.weight"),
        }
        if lt == "linear_attention":
            la = pre + "linear_attn."
            lp["linear_attn"] = {
                "in_proj_qkvz": get(la + "in_proj_qkvz.weight").T,
                "in_proj_ba": get(la + "in_proj_ba.weight").T,
                "conv1d": get(la + "conv1d.weight")[:, 0, :],  # (C, 1, K) -> (C, K)
                "dt_bias": get(la + "dt_bias"),
                "A_log": get(la + "A_log"),
                "norm": get(la + "norm.weight"),
                "out_proj": get(la + "out_proj.weight").T,
            }
        else:
            sa = pre + "self_attn."
            lp["self_attn"] = {
                "q_proj": get(sa + "q_proj.weight").T,
                "k_proj": get(sa + "k_proj.weight").T,
                "v_proj": get(sa + "v_proj.weight").T,
                "o_proj": get(sa + "o_proj.weight").T,
                "q_norm": get(sa + "q_norm.weight"),
                "k_norm": get(sa + "k_norm.weight"),
            }
        if arch.num_experts:
            mp = pre + "mlp."
            E = arch.num_experts
            lp["mlp"] = {
                "router": {"w": get(mp + "gate.weight").T},
                "experts": {
                    "gate_proj": {"w": np.stack(
                        [get(mp + f"experts.{j}.gate_proj.weight").T for j in range(E)]
                    )},
                    "up_proj": {"w": np.stack(
                        [get(mp + f"experts.{j}.up_proj.weight").T for j in range(E)]
                    )},
                    "down_proj": {"w": np.stack(
                        [get(mp + f"experts.{j}.down_proj.weight").T for j in range(E)]
                    )},
                },
                "shared_expert": {
                    "gate_proj": {"w": get(mp + "shared_expert.gate_proj.weight").T},
                    "up_proj": {"w": get(mp + "shared_expert.up_proj.weight").T},
                    "down_proj": {"w": get(mp + "shared_expert.down_proj.weight").T},
                },
                "shared_expert_gate": {"w": get(mp + "shared_expert_gate.weight").T},
            }
        else:
            lp["mlp"] = {
                "gate_proj": get(pre + "mlp.gate_proj.weight").T,
                "up_proj": get(pre + "mlp.up_proj.weight").T,
                "down_proj": get(pre + "mlp.down_proj.weight").T,
            }
        layers.append(lp)

    embed = get("embed_tokens.weight")
    if arch.vocab_pad:
        embed = np.concatenate(
            [embed, np.zeros((arch.vocab_pad, embed.shape[1]), dtype=dt)], axis=0
        )
    params: Dict[str, Any] = {
        "embed_tokens": embed,
        "layers": layers,
        "norm": get("norm.weight"),
    }
    if not arch.tie_word_embeddings:
        head = (
            np.asarray(state_dict["lm_head.weight"], dtype=dt)
            if "lm_head.weight" in state_dict
            else embed[: config.vocab_size]
        )
        if arch.vocab_pad and head.shape[0] < arch.vocab_size:
            head = np.concatenate(
                [head, np.zeros((arch.vocab_pad, head.shape[1]), dtype=dt)], axis=0
            )
        params["lm_head"] = head.T
    return params


def param_specs(config: InferenceConfig):
    """TP layout over the heterogeneous stack. Every sharded dim is
    HEAD-BLOCK aligned so a plain dim shard keeps whole heads per rank:
    ``in_proj_qkvz``/``in_proj_ba`` pack per-K-HEAD blocks (the reshape in
    :func:`_split_qkvz_ba`), so their output dims shard when tp divides
    num_k_heads; the gated-attention q packs (head, 2, D) blocks. Dims that
    don't divide stay replicated (GSPMD reshards activations around them —
    notably the causal conv, whose channel layout is section- not
    head-contiguous and is left replicated on purpose)."""
    from jax.sharding import PartitionSpec as P

    from nxdi_tpu.parallel.mesh import AXIS_MP

    arch = build_arch(config)
    tp = config.tpu_config.tp_degree
    struct = param_shape_struct(config)
    specs = jax.tree_util.tree_map(lambda _: P(), struct)

    def col(ok):  # shard output dim
        return P(None, AXIS_MP) if ok else P()

    def row(ok):  # shard input dim
        return P(AXIS_MP, None) if ok else P()

    gk_ok = tp > 1 and arch.num_k_heads % tp == 0
    gv_ok = tp > 1 and arch.num_v_heads % tp == 0
    h_ok = tp > 1 and arch.num_attention_heads % tp == 0
    kv_ok = tp > 1 and arch.num_kv_heads % tp == 0
    i_ok = tp > 1 and arch.intermediate_size % tp == 0

    if tp > 1:
        specs["embed_tokens"] = P(AXIS_MP, None)  # vocab is tp-padded
        if "lm_head" in specs:
            specs["lm_head"] = P(None, AXIS_MP)
    for li, lt in enumerate(arch.layer_types):
        lp = specs["layers"][li]
        if lt == "linear_attention":
            la = lp["linear_attn"]
            la["in_proj_qkvz"] = col(gk_ok and gv_ok)
            la["in_proj_ba"] = col(gk_ok and gv_ok)
            la["out_proj"] = row(gv_ok)
        else:
            sa = lp["self_attn"]
            sa["q_proj"] = col(h_ok)
            sa["k_proj"] = col(kv_ok)
            sa["v_proj"] = col(kv_ok)
            sa["o_proj"] = row(h_ok)
        mlp = lp["mlp"]
        if arch.num_experts:
            e_ok = tp > 1 and arch.num_experts % tp == 0
            mi_ok = tp > 1 and arch.moe_intermediate_size % tp == 0
            si_ok = tp > 1 and arch.shared_expert_intermediate_size % tp == 0
            ex = mlp["experts"]
            if e_ok:
                for name in ("gate_proj", "up_proj", "down_proj"):
                    ex[name]["w"] = P(AXIS_MP, None, None)
            elif mi_ok:
                ex["gate_proj"]["w"] = P(None, None, AXIS_MP)
                ex["up_proj"]["w"] = P(None, None, AXIS_MP)
                ex["down_proj"]["w"] = P(None, AXIS_MP, None)
            sh = mlp["shared_expert"]
            sh["gate_proj"]["w"] = col(si_ok)
            sh["up_proj"]["w"] = col(si_ok)
            sh["down_proj"]["w"] = row(si_ok)
        else:
            mlp["gate_proj"] = col(i_ok)
            mlp["up_proj"] = col(i_ok)
            mlp["down_proj"] = row(i_ok)
    return specs


def param_shape_struct(config: InferenceConfig):
    from nxdi_tpu.config import to_jax_dtype

    arch = build_arch(config)
    dt = to_jax_dtype(arch.dtype)
    Hd = arch.hidden_size

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    layers = []
    for lt in arch.layer_types:
        lp: Dict[str, Any] = {
            "input_layernorm": s(Hd),
            "post_attention_layernorm": s(Hd),
        }
        if lt == "linear_attention":
            lp["linear_attn"] = {
                "in_proj_qkvz": s(Hd, arch.key_dim * 2 + arch.value_dim * 2),
                "in_proj_ba": s(Hd, arch.num_v_heads * 2),
                "conv1d": s(arch.conv_dim, arch.conv_kernel),
                "dt_bias": s(arch.num_v_heads),
                "A_log": s(arch.num_v_heads),
                "norm": s(arch.head_v_dim),
                "out_proj": s(arch.value_dim, Hd),
            }
        else:
            H, KV, D = arch.num_attention_heads, arch.num_kv_heads, arch.head_dim
            lp["self_attn"] = {
                "q_proj": s(Hd, H * 2 * D),
                "k_proj": s(Hd, KV * D),
                "v_proj": s(Hd, KV * D),
                "o_proj": s(H * D, Hd),
                "q_norm": s(D),
                "k_norm": s(D),
            }
        if arch.num_experts:
            E, I, SI = arch.num_experts, arch.moe_intermediate_size, arch.shared_expert_intermediate_size
            lp["mlp"] = {
                "router": {"w": s(Hd, E)},
                "experts": {
                    "gate_proj": {"w": s(E, Hd, I)},
                    "up_proj": {"w": s(E, Hd, I)},
                    "down_proj": {"w": s(E, I, Hd)},
                },
                "shared_expert": {
                    "gate_proj": {"w": s(Hd, SI)},
                    "up_proj": {"w": s(Hd, SI)},
                    "down_proj": {"w": s(SI, Hd)},
                },
                "shared_expert_gate": {"w": s(Hd, 1)},
            }
        else:
            lp["mlp"] = {
                "gate_proj": s(Hd, arch.intermediate_size),
                "up_proj": s(Hd, arch.intermediate_size),
                "down_proj": s(arch.intermediate_size, Hd),
            }
        layers.append(lp)
    struct = {"embed_tokens": s(arch.vocab_size, Hd), "layers": layers, "norm": s(Hd)}
    if not arch.tie_word_embeddings:
        struct["lm_head"] = s(Hd, arch.vocab_size)
    return struct


# ---------------------------------------------------------------------------
# Application
# ---------------------------------------------------------------------------

def cache_shapes(arch: Qwen3NextArch, batch_size: int, seq_len: int):
    from nxdi_tpu.config import to_jax_dtype

    dt = to_jax_dtype(arch.dtype)
    return {
        "k": ((arch.n_full, batch_size, arch.num_kv_heads, seq_len, arch.head_dim), dt),
        "v": ((arch.n_full, batch_size, arch.num_kv_heads, seq_len, arch.head_dim), dt),
        "conv": ((arch.n_linear, batch_size, arch.conv_dim, arch.conv_kernel), dt),
        "rec": (
            (arch.n_linear, batch_size, arch.num_v_heads, arch.head_k_dim, arch.head_v_dim),
            jnp.float32,
        ),
    }


def make_cache_host(arch: Qwen3NextArch, batch_size: int, seq_len: int):
    return {
        k: jnp.zeros(shape, dt)
        for k, (shape, dt) in cache_shapes(arch, batch_size, seq_len).items()
    }


from nxdi_tpu.runtime.application import TpuModelForCausalLM  # noqa: E402


class Qwen3NextForCausalLM(TpuModelForCausalLM):
    """Application wired to the heterogeneous forward + state cache (the CLI
    resolves it via the family module's APPLICATION_CLS)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        tc = self.tpu_config
        unsupported = [
            ("async_mode", tc.async_mode),
            ("is_prefix_caching", tc.is_prefix_caching),
            ("is_chunked_prefill", tc.is_chunked_prefill),
            ("is_block_kv_layout", tc.is_block_kv_layout),
            ("speculation", tc.speculation_length > 0 or tc.is_medusa),
            ("tensor_capture_config", tc.tensor_capture_config is not None),
            # raw-array param layout: the quantizer/LoRA rewrites would no-op
            ("quantized", tc.quantized),
            ("lora_config", tc.lora_config is not None),
        ]
        bad = [name for name, val in unsupported if val]
        if bad:
            raise ValueError(
                "qwen3_next does not support: " + ", ".join(bad) + " — the "
                "linear-attention recurrence needs dedicated state routing for "
                "these modes (conv/delta states are not paged)"
            )

    def enable_models(self) -> None:
        super().enable_models()
        for wrapper in self.models.values():
            wrapper.forward_fn = qwen3next_forward

    def _arch(self):
        return build_arch(self.config)

    def cache_partition_specs(self):
        from jax.sharding import PartitionSpec as P

        from nxdi_tpu.parallel.mesh import AXIS_MP

        arch = self._arch()
        tp = self.tpu_config.tp_degree
        kv = AXIS_MP if (tp > 1 and arch.num_kv_heads % tp == 0) else None
        gv = AXIS_MP if (tp > 1 and arch.num_v_heads % tp == 0) else None
        return {
            "k": P(None, None, kv, None, None),
            "v": P(None, None, kv, None, None),
            "conv": P(),  # section-contiguous channels: stays replicated
            "rec": P(None, None, gv, None, None),
        }

    def init_cache_host(self):
        tc = self.tpu_config
        return make_cache_host(
            self._arch(), tc.kv_cache_batch_size + tc.kv_cache_padding_size, tc.seq_len
        )

    def _cache_struct(self):
        tc = self.tpu_config
        shapes = cache_shapes(
            self._arch(), tc.kv_cache_batch_size + tc.kv_cache_padding_size, tc.seq_len
        )
        return {k: jax.ShapeDtypeStruct(shape, dt) for k, (shape, dt) in shapes.items()}


APPLICATION_CLS = Qwen3NextForCausalLM
