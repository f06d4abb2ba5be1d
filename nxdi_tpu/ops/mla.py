"""Multi-head Latent Attention (MLA) — deepseek lineage.

Reference: models/deepseek/modeling_deepseek.py:79 ``DeepseekV3Attention``
(q LoRA path :172-186, compressed kv :188-199, yarn rope rope_util.py) —
re-designed around a LATENT KV cache instead of the reference's expanded
per-head cache:

  - the cache's ``k`` stores the ROTATED shared rope key (B, 1, S, qk_rope),
    its ``v`` the rms-normed compressed kv latent (B, 1, S, kv_lora) —
    per-position cache cost is ``kv_lora + qk_rope`` (e.g. 576 for V3) instead
    of ``heads * (qk_nope + qk_rope + v_dim)``, the whole point of MLA;
  - context encoding expands the latent through ``kv_b`` to per-head
    k_nope/value (the non-absorbed formulation — mathematically identical to
    HF eager);
  - token generation over the PAGED latent pool is absorbed: ``q_nope`` goes
    through ``W_UK`` (one half of ``kv_b``) into the latent space, scores and
    the weighted sum run over the cached rows as they lie, the result comes
    out through ``W_UV`` (the other half). Its core over the pool is the
    Pallas kernel ``mla_paged_decode`` (ops/kernels/mla_decode.py);
    ``absorbed_decode_xla`` over the gathered blocks is the CPU path.

Head sharding: MLA has no GQA — q/kv_b/o shard over heads, which must divide
tp (the reference asserts the same, modeling_deepseek.py:137).

``rope_interleave`` checkpoints (deepseek stores rope channels interleaved)
are handled at CONVERSION time by permuting the rope-dim output columns of
q(_b) and kv_a, so the runtime always uses the standard rotate-half.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from nxdi_tpu.ops import attention as attn_ops
from nxdi_tpu.ops import attention_select
from nxdi_tpu.ops.norms import rms_norm
from nxdi_tpu.ops.rope import apply_rotary_pos_emb
from nxdi_tpu.parallel.mesh import AXIS_MP


@dataclass(frozen=True)
class MLAArch:
    num_heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    softmax_scale: float

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


#: the paged pool stores the rope key in rows of whole lane tiles: a row
#: narrower than 128 lanes is padded to 128 by the TPU's tiled layout anyway,
#: and the decode kernel's block copies want lane-aligned rows
LANE = 128


def paged_latent_widths(mla: MLAArch) -> Tuple[int, int]:
    """(``k`` width, ``v`` width) of one token's row in the paged latent pool:
    the rotated rope key padded with zeros to a lane tile, and the normed
    latent. 128 + 512 values = 1280 B a token a layer in bf16 for the V3
    lineage's 64 + 512."""
    return -(-mla.qk_rope_head_dim // LANE) * LANE, mla.kv_lora_rank


def absorbed_weights(mla: MLAArch, p_attn: Dict[str, Any], dtype):
    """``kv_b`` (r, H * (nope + v)) as its two halves ``W_UK`` (r, H, nope) and
    ``W_UV`` (r, H, v): views of the one weight, taken at trace."""
    from nxdi_tpu.ops.quantization import materialize_weight

    w = materialize_weight(p_attn["kv_b"], dtype).reshape(
        mla.kv_lora_rank, mla.num_heads, mla.qk_nope_head_dim + mla.v_head_dim
    )
    return w[..., : mla.qk_nope_head_dim], w[..., mla.qk_nope_head_dim:]


def absorbed_decode_xla(q_lat, q_rot, k_rot_all, c_all, q_pos, kv_pos, scale):
    """The absorbed decode core in plain XLA over GATHERED latent rows: the
    CPU path, and what ``mla_paged_decode`` is tested against.

    q_lat (B, H, r), q_rot (B, H, rope_d), k_rot_all (B, W, >= rope_d),
    c_all (B, W, r), q_pos (B,), kv_pos (B, W) -> o_lat (B, H, r)."""
    rope_d = q_rot.shape[-1]
    s = jnp.einsum("bhr,bwr->bhw", q_lat, c_all, preferred_element_type=jnp.float32)
    s = s + jnp.einsum(
        "bhd,bwd->bhw", q_rot, k_rot_all[..., :rope_d], preferred_element_type=jnp.float32
    )
    mask = attn_ops.causal_mask_from_positions(q_pos[:, None], kv_pos)  # (B, 1, W)
    s = jnp.where(mask, s * scale, attn_ops.NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(c_all.dtype)
    return jnp.einsum("bhw,bwr->bhr", p, c_all)


def _expanded_core(name, mla, qq, kk, v, position_ids, kv_pos, policy):
    """Non-absorbed attention over per-head keys and values: the flash prefill
    kernel, with the values padded to the qk width (one head width is all it
    knows), where the attention table chose it; else XLA."""
    if name == "cte_flash_kernel":
        from nxdi_tpu.ops import kernels as attn_kernels

        v_wide = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qq.shape[-1] - mla.v_head_dim)))
        ctx = attn_kernels.sharded_kernel_call(
            policy, qq, kk, v_wide, position_ids, kv_pos, decode=False,
            scale=mla.softmax_scale,
        )
        return ctx[..., : mla.v_head_dim]
    mask = attn_ops.causal_mask_from_positions(position_ids, kv_pos)
    return attn_ops.grouped_attention(
        qq, kk, v, mask, scale=mla.softmax_scale, softmax_dtype=jnp.float32
    )  # (B, H, S, v_dim)


def mla_attention_block(
    arch,  # DecoderArch with .mla set
    p_attn: Dict[str, Any],
    hidden: jax.Array,  # (B, S, hidden)
    cos: jax.Array,
    sin: jax.Array,
    k_cache_l: jax.Array,  # contiguous: (B, 1, S_max, qk_rope) rotated rope keys;
    v_cache_l: jax.Array,  # (B, 1, S_max, kv_lora) normed latents. Paged: the
    # WHOLE pools (L, slots, 1, rope padded to a lane tile) / (L, slots, 1, kv_lora)
    position_ids: jax.Array,
    cache_spec,
    attend_to_cache: bool,
    policy,
    layout,
    cache_inputs: Optional[Dict[str, jax.Array]] = None,
    adapter_ids: Optional[jax.Array] = None,
    window_enabled=None,
    use_rope=None,
    layer_idx=None,  # GLOBAL layer index: the paged pool's layer
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    from nxdi_tpu.kvcache.kv_cache import BlockKVLayout
    from nxdi_tpu.models.base import _linear

    mla: MLAArch = arch.mla
    B, S, _ = hidden.shape
    H = mla.num_heads
    nope, rope_d, r = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.kv_lora_rank
    aq, ac = arch.act_quant, arch.act_clamp
    paged = isinstance(layout, BlockKVLayout)

    with jax.named_scope("attn.qkv"):
        # -- queries
        if mla.q_lora_rank is None:
            q = _linear(hidden, p_attn["q_proj"], aq, ac)
        else:
            qa = _linear(hidden, p_attn["q_a"], aq, ac)
            qa = rms_norm(qa, p_attn["q_a_norm"], arch.rms_norm_eps)
            q = _linear(qa, p_attn["q_b"], aq, ac)
        q = q.reshape(B, S, H, mla.qk_head_dim)
        q_nope, q_rot = q[..., :nope], q[..., nope:]

        # -- compressed kv + shared rope key
        ckv = _linear(hidden, p_attn["kv_a"], aq, ac)  # (B, S, r + rope_d)
        c, k_rot = ckv[..., :r], ckv[..., r:]
        c = rms_norm(c, p_attn["kv_a_norm"], arch.rms_norm_eps)  # normed BEFORE caching

    with jax.named_scope("attn.rope"):
        q_rot = jnp.swapaxes(q_rot, 1, 2)  # (B, H, S, rope_d)
        k_rot = k_rot[:, None]  # (B, 1, S, rope_d)
        q_rot, k_rot = apply_rotary_pos_emb(q_rot, k_rot, cos, sin)

    # -- latent cache update (k <- rotated rope key, v <- normed latent)
    # layouts expect (B, KV, S, D): rope key (B, 1, S, rope_d), latent (B, 1, S, r)
    ci = dict(cache_inputs or {})
    ci["position_ids"] = position_ids
    if layer_idx is not None:
        ci["layer_idx"] = layer_idx
    with jax.named_scope("kv.write"):
        k_store = k_rot
        if paged and k_cache_l.shape[-1] > rope_d:  # the pool's rows are lane tiles
            k_store = jnp.pad(
                k_rot, ((0, 0), (0, 0), (0, 0), (0, k_cache_l.shape[-1] - rope_d))
            )
        new_k, new_v = layout.update(k_cache_l, v_cache_l, k_store, c[:, None], ci, cache_spec)

    def o_proj(ctx):  # (B, S, H * v_dim)
        with jax.named_scope("attn.out"):
            return _linear(ctx, p_attn["o_proj"], aq, ac)

    # which attention computes this call (ops/attention_select.py): the
    # absorbed form over the paged pool for one token, else the expanded one
    site = attention_select.site_of(
        arch, layout, policy, ci, (B, H, S, mla.qk_head_dim), (B, H, S, mla.qk_head_dim),
        new_k, cache_spec.compute_dtype, attend_to_cache=attend_to_cache, v_cache=new_v,
    )
    name = attention_select.select(site)

    if site.mla == "absorbed":
        # -- token generation over the paged latent pool, ABSORBED: the query
        # goes into the latent space (q_nope @ W_UK), scores and the weighted
        # sum run over the cached 512 + 64-wide rows as they lie, and only the
        # result comes back out through W_UV: 0.28 MFLOP a cached token a
        # layer where expanding every cached row through kv_b costs 33
        dt = hidden.dtype
        w_uk, w_uv = absorbed_weights(mla, p_attn, dt)
        with jax.named_scope("attn.q_absorb"):
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk).astype(dt)
        q_pos = position_ids[:, 0].astype(jnp.int32)
        with jax.named_scope("attn.core"):
            if name == "tkg_mla_paged_kernel":
                from nxdi_tpu.ops.kernels import mla_decode

                o_lat = mla_decode.sharded_mla_paged_decode_call(
                    policy, q_lat, q_rot[:, :, 0], new_k, new_v,
                    ci["block_table"], q_pos, layer_idx,
                    block_size=layout.block_size, scale=mla.softmax_scale,
                )
            else:
                k_all, c_all, kv_pos = layout.read(new_k, new_v, ci, cache_spec)
                o_lat = absorbed_decode_xla(
                    q_lat, q_rot[:, :, 0], k_all[:, 0], c_all[:, 0], q_pos, kv_pos,
                    mla.softmax_scale,
                )
        with jax.named_scope("attn.v_up"):
            ctx = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt), w_uv).astype(dt)
        return o_proj(ctx.reshape(B, 1, H * mla.v_head_dim)), (new_k, new_v)

    with jax.named_scope("attn.core"):
        if attend_to_cache:
            k_rot_all, c_all, kv_pos = layout.read(new_k, new_v, ci, cache_spec)
            k_rot_all = k_rot_all[..., :rope_d]
        else:
            k_rot_all, c_all = k_rot, c[:, None]
            kv_pos = position_ids

        # -- expand latent to per-head k_nope / value through kv_b
        W = c_all.shape[2]
        kb = _linear(c_all[:, 0], p_attn["kv_b"], aq, ac)  # (B, W, H*(nope+v))
        kb = kb.reshape(B, W, H, nope + mla.v_head_dim)
        k_nope = jnp.swapaxes(kb[..., :nope], 1, 2)  # (B, H, W, nope)
        v = jnp.swapaxes(kb[..., nope:], 1, 2)  # (B, H, W, v_dim)

        qq = jnp.concatenate([jnp.swapaxes(q_nope, 1, 2), q_rot], axis=-1)  # (B,H,S,qk)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rot_all, (B, H, W, rope_d))], axis=-1
        )
        ctx = _expanded_core(name, mla, qq, kk, v, position_ids, kv_pos, policy)

    ctx = jnp.swapaxes(ctx, 1, 2).reshape(B, S, H * mla.v_head_dim)
    return o_proj(ctx), (new_k, new_v)


# ---------------------------------------------------------------------------
# Param layout / conversion helpers (used by the deepseek family module)
# ---------------------------------------------------------------------------

def mla_param_specs(mla: MLAArch) -> Dict[str, Any]:
    specs: Dict[str, Any] = {
        "kv_a": {"w": P()},  # small (hidden -> r + rope): replicated
        "kv_a_norm": P(),
        "kv_b": {"w": P(None, AXIS_MP)},  # heads on out dim
        "o_proj": {"w": P(AXIS_MP, None)},
    }
    if mla.q_lora_rank is None:
        specs["q_proj"] = {"w": P(None, AXIS_MP)}
    else:
        specs["q_a"] = {"w": P()}
        specs["q_a_norm"] = P()
        specs["q_b"] = {"w": P(None, AXIS_MP)}
    return specs


def mla_shape_struct(mla: MLAArch, hidden_size: int, num_layers: int, dtype) -> Dict[str, Any]:
    def s(*shape):
        return jax.ShapeDtypeStruct((num_layers,) + shape, dtype)

    H, hs = mla.num_heads, hidden_size
    struct: Dict[str, Any] = {
        "kv_a": {"w": s(hs, mla.kv_lora_rank + mla.qk_rope_head_dim)},
        "kv_a_norm": s(mla.kv_lora_rank),
        "kv_b": {"w": s(mla.kv_lora_rank, H * (mla.qk_nope_head_dim + mla.v_head_dim))},
        "o_proj": {"w": s(H * mla.v_head_dim, hs)},
    }
    if mla.q_lora_rank is None:
        struct["q_proj"] = {"w": s(hs, H * mla.qk_head_dim)}
    else:
        struct["q_a"] = {"w": s(hs, mla.q_lora_rank)}
        struct["q_a_norm"] = s(mla.q_lora_rank)
        struct["q_b"] = {"w": s(mla.q_lora_rank, H * mla.qk_head_dim)}
    return struct


def deinterleave_rope_columns(w_t: np.ndarray, head_dim: int, nope: int, rope_d: int) -> np.ndarray:
    """Permute the rope-dim output columns of a per-head projection weight
    (already transposed to (in, H*head_dim)) from interleaved [r0,i0,r1,i1,...]
    to rotate-half [r0,r1,...,i0,i1,...] layout (HF rope_interleave handling,
    done once at conversion instead of per step)."""
    fin, out = w_t.shape
    H = out // head_dim
    w = w_t.reshape(fin, H, head_dim)
    rope_part = w[..., nope:]
    perm = np.concatenate([np.arange(0, rope_d, 2), np.arange(1, rope_d, 2)])
    w = np.concatenate([w[..., :nope], rope_part[..., perm]], axis=-1)
    return w.reshape(fin, out)
