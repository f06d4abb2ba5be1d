"""MiniCPM-SALA: a decoder that mixes block-sparse softmax attention layers
(``mixer_types[l] == "minicpm4"``: InfLLM-V2, arXiv:2509.24663) and lightning
linear-attention layers (``"lightning-attn"``, arXiv:2401.04658), with the
MiniCPM family's mu-P scalings (models/minicpm).

Served on the paged path only. The cache tree has three kinds of leaf:

- ``k`` / ``v``: the block pool of the SPARSE layers, by block table (the
  engine's ``BlockSpaceManager``, unchanged). A block holds, one KV head after
  the other, that head's ``pa_block_size`` rows: the two heads of a group choose
  different blocks, so a (block, head) is one contiguous run of rows and the
  pool's ``(L, blocks * KV * block, D)`` view is a pool of one-head blocks,
  block ``b`` of head ``g`` at entry ``b * KV + g``. ``pa_block_size`` is the
  selection's block: a selected block IS a table entry.
- ``kc``: the sparse layers' INDEX of compressed keys (one row a
  ``kernel_stride`` tokens), ``(L, slots, rows, KV, D)`` by slot id.
- ``lin_state``: the lightning layers' float32 state ``(L, slots + 1, H, D, D)``
  by slot id; the last slot is spare, where a batch's padding rows land.

A decode step of a sparse layer writes its row into the pool, completes an index
row when a window of ``kernel_size`` tokens ends, scores the slot's live index
rows, and reads the pool through a COMPACT table a (row, KV head)
(ops/block_select.py ``decode_tables``) with the paged decode kernel as it is: a
row past ``dense_len`` reads ``topk`` blocks, whatever its length. A fresh
prefill builds the index by one strided mean and hands the flash kernel the
selection as a block mask. A lightning layer has no attention call: a prefill
runs the chunked form and leaves the state at the prompt's last token, a decode
step updates the slot's state in place (ops/linear_attention.py).

One pipeline stage of a deeper model: ``first_hidden_layer`` and
``num_hidden_layers_total`` place the ``num_hidden_layers`` layers held here in
the published depth (``mixer_types`` stays whole; the residual multiplier and
the decay's layer factor are the published depth's).

HF weight layout (assumed from the family's): ``self_attn.{q,k,v,o}_proj``,
``self_attn.o_gate`` (the output gate), ``self_attn.{q,k}_norm``,
``self_attn.o_norm`` (hidden wide) on lightning layers, ``mlp.{gate,up,down}_proj``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nxdi_tpu.config import InferenceConfig
from nxdi_tpu.models import dense
from nxdi_tpu.models.base import DecoderArch
from nxdi_tpu.models.minicpm.modeling_minicpm import MiniCPMInferenceConfig
from nxdi_tpu.ops.block_select import BlockSelectConfig
from nxdi_tpu.ops.linear_attention import decay_rates
from nxdi_tpu.ops.rope import inv_freq_from_hf_config

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


class MiniCPMSALAInferenceConfig(MiniCPMInferenceConfig):
    REQUIRED = [
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size", "mixer_types", "sparse_config", "lightning_nh",
        "lightning_nkv", "lightning_head_dim",
    ]

    #: the published switches this family's layers are written for
    SWITCHES = dict(
        attn_use_rope=False, lightning_use_rope=True, qk_norm=True, use_output_gate=True,
        use_output_norm=True, attn_use_output_gate=True, lightning_scale="1/sqrt(d)",
    )

    COMPUTES_BLOCK_SELECTION = True

    def add_derived_config(self):
        for name, default in (("first_hidden_layer", 0),
                              ("num_hidden_layers_total", self.num_hidden_layers)):
            if getattr(self, name, None) is None:
                setattr(self, name, default)
        super().add_derived_config()
        for key, want in self.SWITCHES.items():
            if getattr(self, key, want) != want:
                raise NotImplementedError(f"minicpm_sala is written for {key} = {want!r}")
        if self.lightning_nkv != self.lightning_nh:
            raise NotImplementedError("minicpm_sala: grouped lightning heads")
        first, n = self.first_hidden_layer, self.num_hidden_layers
        held = list(self.mixer_types)[first: first + n]
        if len(held) != n or set(held) - {SPARSE, LIGHTNING}:
            raise ValueError(
                f"mixer_types has no layers {first}..{first + n} of kinds {SPARSE}/{LIGHTNING}"
            )


@dataclass(frozen=True)
class SALAArch:
    """The two kinds of layer and the depth-ordered walk. Each schedule entry:
    (kind, type_lo, type_hi, seg_idx): the half-open range of the kind's own
    layer indices (into its cache leaves) and the stacked params segment."""

    sparse: DecoderArch
    lightning: DecoderArch
    schedule: Tuple[Tuple[str, int, int, int], ...]
    select: BlockSelectConfig
    rates: Tuple[Tuple[float, ...], ...]  # -log(decay) a (lightning layer, head)

    #: stores held per SLOT beside the pool: their programs' batches carry the
    #: rows' slot ids (runtime/model_wrapper.py ``per_slot_cache``), and every
    #: program takes them row-major as declared, in the TPU tiling named here
    #: (``_pinned_cache_layouts``; left to each program, the prefill kept the
    #: index KV-major and every prefill relaid it twice): an index row's (KV, D)
    #: a tile of its own, the state's (D, D) in (8, 128) tiles
    slot_cache_keys = {"kc": ((2, 128), (2, 1)), "lin_state": ((8, 128),)}
    #: the axis of a store that counts its rows a slot (serving/engine.py ``_kv_held``)
    slot_rows_axis = 2

    def kv_cache_spec(self, batch_size, max_len, quant_dtype=None):
        return self.sparse.kv_cache_spec(batch_size, max_len, quant_dtype=quant_dtype)

    @property
    def num_layers(self):
        return self.sparse.num_layers + self.lightning.num_layers

    def __getattr__(self, name):
        # the runtime reads generic decoder attrs (vocab, dtype, sampler wiring)
        return getattr(object.__getattribute__(self, "sparse"), name)


def held_kinds(config) -> list:
    first = config.first_hidden_layer
    return list(config.mixer_types)[first: first + config.num_hidden_layers]


def build_arch(config: InferenceConfig, **overrides) -> SALAArch:
    if config.tpu_config.tp_degree != 1:
        raise NotImplementedError("minicpm_sala does not support tensor parallel yet")
    kinds = held_kinds(config)
    dim_base = getattr(config, "dim_model_base", None) or config.hidden_size
    total = config.num_hidden_layers_total
    common = dict(
        embed_scale=float(getattr(config, "scale_emb", 1.0)),
        # the PUBLISHED depth's: a stage of the model keeps the model's multiplier
        residual_multiplier=float(getattr(config, "scale_depth", 1.0)) / math.sqrt(total),
        logits_scaling=float(config.hidden_size) / float(dim_base),
        qk_norm=True,
        attn_out_gate=True,
        **overrides,
    )
    sparse = dense.build_arch(
        config, num_layers=kinds.count(SPARSE), no_rope=True,
        attention_scale=float(config.head_dim) ** -0.5, **common,
    )
    lightning = dense.build_arch(
        config, num_layers=kinds.count(LIGHTNING), num_attention_heads=config.lightning_nh,
        num_kv_heads=config.lightning_nkv, head_dim=config.lightning_head_dim, **common,
    )
    schedule, counters, prev = [], {SPARSE: 0, LIGHTNING: 0}, None
    for kind in kinds:
        lo = counters[kind]
        if kind == prev:
            t, a, b, s = schedule[-1]
            schedule[-1] = (t, a, b + 1, s)
        else:
            schedule.append((kind, lo, lo + 1, len(schedule)))
            prev = kind
        counters[kind] += 1
    first = config.first_hidden_layer
    rates = tuple(
        tuple(float(r) for r in decay_rates(config.lightning_nh, first + i, total))
        for i, kind in enumerate(kinds) if kind == LIGHTNING
    )
    return SALAArch(
        sparse=sparse, lightning=lightning, schedule=tuple(schedule),
        select=BlockSelectConfig.from_dict(config.sparse_config), rates=rates,
    )


def build_inv_freq(config: InferenceConfig) -> np.ndarray:
    # the lightning layers' rope: every channel of a head, no scaling
    return inv_freq_from_hf_config(
        config.lightning_head_dim, getattr(config, "rope_theta", 10000.0), None
    )


# ---------------------------------------------------------------------------
# The two kinds of layer
# ---------------------------------------------------------------------------

#: ``jax.named_scope`` of the two kinds of segment
_SCOPE = {SPARSE: "layers.sparse", LIGHTNING: "layers.lightning"}


def _project(t: DecoderArch, p_attn, x, out_dtype=None):
    """q (B, H, S, D), k, v (B, KV, S, D): projected, q and k normed a head.
    ``out_dtype``: the projections' own dtype (float32: the matmul's accumulator
    as it is, where what follows is ill-conditioned in bf16)."""
    from nxdi_tpu.models.base import _norm

    B, S, _ = x.shape
    H, KV, D = t.num_attention_heads, t.num_kv_heads, t.head_dim

    def proj(name, heads):
        y = jnp.dot(x, p_attn[name]["w"], preferred_element_type=out_dtype or x.dtype)
        return y.reshape(B, S, heads, D)

    with jax.named_scope("attn.qkv"):
        q = _norm(t, proj("q_proj", H), p_attn["q_norm"])
        k = _norm(t, proj("k_proj", KV), p_attn["k_norm"])
        v = proj("v_proj", KV)
    return tuple(jnp.swapaxes(a, 1, 2) for a in (q, k, v))


def _gated_out(p_attn, x, ctx):
    """``W_o (ctx * sigmoid(W_g x))``: the output gate, elementwise, hidden wide."""
    from nxdi_tpu.models.base import _linear

    with jax.named_scope("attn.out"):
        g = jax.nn.sigmoid(_linear(x, p_attn["gate_proj"]).astype(jnp.float32))
        return _linear((ctx.astype(jnp.float32) * g).astype(x.dtype), p_attn["o_proj"])


def _pool_rows(slots, KV: int, block: int):
    """Flat pool slots (...,) -> rows (..., KV) of the one-head-blocks view:
    block ``b`` of head ``g`` holds rows ``(b * KV + g) * block ..``."""
    g = jnp.arange(KV, dtype=jnp.int32)
    return (slots // block)[..., None] * (KV * block) + g * block + (slots % block)[..., None]


def _sparse_attention(arch: SALAArch, p_attn, x, pool, kc, li, position_ids, ci, attend_to_cache,
                      policy, layout):
    """One sparse layer's mixer: ``(out (B, S, hidden), pool, kc, (read, live))``."""
    from nxdi_tpu.ops import attention as attn_ops
    from nxdi_tpu.ops import attention_select as attn_select
    from nxdi_tpu.ops import block_select
    from nxdi_tpu.ops import kernels as attn_kernels

    t, cfg = arch.sparse, arch.select
    B, S, _ = x.shape
    H, KV, D = t.num_attention_heads, t.num_kv_heads, t.head_dim
    G, bs = H // KV, layout.block_size
    scale = t.attention_scale
    q, k, v = _project(t, p_attn, x)
    k_pool, v_pool = pool
    L, n_slots = k_pool.shape[:2]
    views = [a.reshape(L, n_slots * KV, D) for a in (k_pool, v_pool)]
    slot_ids = ci["seq_ids"].astype(jnp.int32)
    pos = position_ids.astype(jnp.int32)

    with jax.named_scope("kv.write"):
        slots = ci["slot_mapping"].astype(jnp.int32)  # (B, S), -1: padding
        rows = jnp.where(slots[..., None] < 0, n_slots * KV, _pool_rows(slots, KV, bs)).reshape(-1)
        views = [
            view.at[li, rows].set(
                jnp.moveaxis(new, 1, 2).reshape(-1, D).astype(view.dtype), mode="drop")
            for view, new in zip(views, (k, v))
        ]
    counts = (jnp.int32(0), jnp.int32(0))
    select_ci = dict(ci)
    if attend_to_cache:
        if S != 1:
            raise NotImplementedError("minicpm_sala: a cache-attending prefill selects no blocks yet")
        p = pos[:, 0]
        bt = ci["block_table"].astype(jnp.int32)
        with jax.named_scope("attn.index"):
            # a window of kernel_size tokens that ends here: its mean goes into the index
            done = p + 1 - cfg.kernel_size
            at = jnp.maximum(p[:, None] - jnp.arange(cfg.kernel_size - 1, -1, -1, dtype=jnp.int32), 0)
            phys = jnp.take_along_axis(bt, at // bs, axis=1)  # (B, kernel)
            src = _pool_rows(jnp.maximum(phys, 0) * bs + at % bs, KV, bs)  # (B, kernel, KV)
            mean = views[0][li, src].astype(jnp.float32).mean(axis=1)  # (B, KV, D)
            j = jnp.where((done >= 0) & (done % cfg.kernel_stride == 0),
                          done // cfg.kernel_stride, kc.shape[2])
            kc = kc.at[li, slot_ids, j].set(mean.astype(kc.dtype), mode="drop")
        tables, q_pos, read, live = block_select.decode_tables(
            q[:, :, 0].reshape(B, KV, G, D), kc[li, slot_ids], p, bt, scale, cfg
        )
        counts = (read.sum().astype(jnp.int32), (live.sum() * KV).astype(jnp.int32))
        # the pool as one-head blocks: a (row, KV head) is a row of the kernel
        head = jnp.arange(KV, dtype=jnp.int32)[None, :, None]
        tables = jnp.where(tables >= 0, tables * KV + head, -1).reshape(B * KV, -1)
        select_ci.update(block_select=tables, block_table=tables)
        q1 = q.reshape(B * KV, G, 1, D)
        pools = [view.reshape(L, n_slots * KV, 1, D) for view in views]
        site = attn_select.site_of(
            t, layout, policy, select_ci, q1.shape, None, pools[0], q.dtype,
            attend_to_cache=True, v_cache=pools[1],
        )
        name = attn_select.select(site)
        if name != "tkg_paged_kernel":
            raise NotImplementedError(f"minicpm_sala decodes through the paged kernel, not {name}")
        with jax.named_scope("attn.core"):
            ctx = attn_kernels.sharded_paged_decode_call(
                policy, q1, pools[0], pools[1], tables, q_pos.reshape(B * KV, 1), li,
                block_size=bs, scale=scale,
            ).reshape(B, H, 1, D)
    else:
        with jax.named_scope("attn.index"):
            fresh = block_select.compress_keys(k, cfg).astype(kc.dtype)  # (B, J, KV, D)
            for b in range(B):
                kc = jax.lax.dynamic_update_slice(
                    kc, fresh[b][None, None], (li, slot_ids[b], 0, 0, 0))
        mask = None
        if S > cfg.dense_len:
            mask = block_select.prefill_mask(q.reshape(B, KV, G, S, D), fresh, pos, scale, cfg)
            select_ci["block_select"] = mask
        site = attn_select.site_of(
            t, layout, policy, select_ci, q.shape, k.shape, pool[0], q.dtype, attend_to_cache=False,
        )
        name = attn_select.select(site)
        with jax.named_scope("attn.core"):
            if name == "cte_flash_kernel":
                ctx = attn_kernels.sharded_kernel_call(
                    policy, q, k, v, pos, pos, decode=False, scale=scale, block_mask=mask)
            elif name == "cte_xla":  # no selection here: the table refused it above
                ctx = attn_ops.attention_with_positions(q, k, v, pos, pos, scale=scale)
            else:
                raise NotImplementedError(f"minicpm_sala prefills through {name}?")
    ctx = jnp.swapaxes(ctx, 1, 2).reshape(B, S, H * D)
    pool = tuple(view.reshape(k_pool.shape) for view in views)
    return _gated_out(p_attn, x, ctx), pool, kc, counts


def _lightning_mixer(arch: SALAArch, p_attn, x, store, li, rates, cos, sin, ci, attend_to_cache):
    """One lightning layer's mixer: ``(out (B, S, hidden), store)``. No attention call."""
    from nxdi_tpu.models.base import _norm
    from nxdi_tpu.ops import linear_attention
    from nxdi_tpu.ops.rope import apply_rotary_pos_emb

    t = arch.lightning
    B, S, _ = x.shape
    H, D = t.num_attention_heads, t.head_dim
    # float32 from the projections on: the state is float32, and what feeds it
    # and reads it is not rounded to bf16 on the way
    q, k, v = _project(t, p_attn, x, jnp.float32)
    with jax.named_scope("attn.rope"):
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
    q = (q.astype(jnp.float32) * D ** -0.5)
    slot_ids = ci["seq_ids"].astype(jnp.int32)
    if attend_to_cache:
        if S != 1:
            raise NotImplementedError("minicpm_sala: a cache-attending prefill of the state")
        # a batch's padding rows repeat a row's slot: they go to the spare one
        i = jnp.arange(B)
        twin = ((slot_ids[:, None] == slot_ids[None, :]) & (i[None, :] < i[:, None])).any(axis=1)
        slots = jnp.where(twin, store.shape[1] - 1, slot_ids)
        o, store = linear_attention.decode_step(
            store, li, slots, q[:, :, 0], k[:, :, 0], v[:, :, 0], rates)
        o = o[:, None]  # (B, 1, H, D)
    else:
        last = ci.get("last_token_index")
        last = jnp.full((B,), S - 1, jnp.int32) if last is None else last.astype(jnp.int32)
        o, states = linear_attention.chunked_prefill(
            *(jnp.swapaxes(a, 1, 2) for a in (q, k, v)), rates, last)
        with jax.named_scope("lin.state"):
            store = linear_attention.write_states(store, li, slot_ids, states)
    with jax.named_scope("lin.out"):
        # ONE norm over all the heads' channels (MiniMax-01's lightning attention): a
        # norm a head would leave only the SIGN of q . k where a state is one token old
        o = _norm(t, o.reshape(B, S, H * D), p_attn["o_norm"]).astype(x.dtype)
    return _gated_out(p_attn, x, o), store


def _walk(arch: SALAArch, params, hidden, cos, sin, cache, position_ids, attend_to_cache,
          policy, layout, ci):
    """The segments in depth order, each kind's cache WHOLE into every segment
    as the layer scan's carry, written at ``(layer, ...)`` in place."""
    from nxdi_tpu.models.base import _norm, constrain, mlp_block

    pool, kc, store = (cache["k"], cache["v"]), cache["kc"], cache["lin_state"]
    counts = (jnp.int32(0), jnp.int32(0))
    rates_all = jnp.asarray(np.asarray(arch.rates, np.float32).reshape(-1, arch.lightning.num_attention_heads))

    def finish(t, lp, h, mixed):
        m = t.residual_multiplier
        h = h + mixed * m
        ff = mlp_block(t, lp["mlp"], _norm(t, h, lp["post_attention_layernorm"]), policy=policy)
        return constrain(h + ff * m, policy.hidden)

    for kind, lo, hi, seg_idx in arch.schedule:
        seg = params["segments"][seg_idx]
        idx = jnp.arange(lo, hi, dtype=jnp.int32)
        with jax.named_scope(_SCOPE[kind]):
            if kind == SPARSE:
                t = arch.sparse

                def body(carry, xs, t=t):
                    h, pool_, kc_, counts_ = carry
                    lp, li = xs
                    x = _norm(t, h, lp["input_layernorm"])
                    mixed, pool_, kc_, step = _sparse_attention(
                        arch, lp["attn"], x, pool_, kc_, li, position_ids, ci, attend_to_cache,
                        policy, layout)
                    counts_ = (counts_[0] + step[0], counts_[1] + step[1])
                    return (finish(t, lp, h, mixed), pool_, kc_, counts_), None

                (hidden, pool, kc, counts), _ = jax.lax.scan(body, (hidden, pool, kc, counts), (seg, idx))
                # a short segment is no loop the compiler keeps: without this it may
                # recompute the write on the pool it was handed and hold two pools
                hidden, pool, kc = jax.lax.optimization_barrier((hidden, pool, kc))
            else:
                t = arch.lightning

                def body(carry, xs, t=t):
                    h, store_ = carry
                    lp, li, rates = xs
                    x = _norm(t, h, lp["input_layernorm"])
                    mixed, store_ = _lightning_mixer(
                        arch, lp["attn"], x, store_, li, rates, cos, sin, ci, attend_to_cache)
                    return (finish(t, lp, h, mixed), store_), None

                (hidden, store), _ = jax.lax.scan(body, (hidden, store), (seg, idx, rates_all[lo:hi]))
                hidden, store = jax.lax.optimization_barrier((hidden, store))
    return hidden, {"k": pool[0], "v": pool[1], "kc": kc, "lin_state": store}, counts


def causal_lm_forward(
    arch: SALAArch,
    inv_freq,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    *,
    attend_to_cache: bool,
    kv_window=None,
    policy=None,
    layout=None,
    gather_last_token: bool = True,
    output_logits: bool = False,
    output_all_logits: bool = False,
    on_device_sampling: bool = True,
    do_sample: bool = False,
    global_topk: int = 256,
    deterministic: bool = False,
    **_unused,
):
    from nxdi_tpu.config import to_jax_dtype
    from nxdi_tpu.kvcache.kv_cache import BlockKVLayout
    from nxdi_tpu.models.base import collect_cache_inputs, constrain
    from nxdi_tpu.ops import sampling as sampling_ops
    from nxdi_tpu.ops.norms import rms_norm
    from nxdi_tpu.ops.rope import rope_cos_sin
    from nxdi_tpu.parallel.policy import DEFAULT_POLICY

    policy = policy or DEFAULT_POLICY
    if not isinstance(layout, BlockKVLayout):
        raise NotImplementedError("minicpm_sala is served over the block KV layout only")
    ci = collect_cache_inputs(batch)
    if "seq_ids" not in ci or "slot_mapping" not in ci:
        raise ValueError(
            "the index and the state are addressed by the rows' slot ids: the batch needs "
            "seq_ids beside block_table / slot_mapping"
        )
    t = arch.sparse
    compute_dtype = to_jax_dtype(t.dtype)
    input_ids, position_ids = batch["input_ids"], batch["position_ids"]
    B = input_ids.shape[0]

    hidden = jnp.take(params["embed_tokens"], input_ids, axis=0).astype(compute_dtype)
    hidden = hidden * jnp.asarray(t.embed_scale, compute_dtype)
    hidden = constrain(hidden, policy.hidden)
    cos, sin = rope_cos_sin(position_ids, np.asarray(inv_freq))
    hidden, new_cache, counts = _walk(
        arch, params, hidden, cos, sin, cache, position_ids, attend_to_cache, policy, layout, ci)

    hidden = rms_norm(hidden, params["norm"], t.rms_norm_eps)
    if gather_last_token and not output_all_logits:
        idx = batch["last_token_index"][:, None, None]
        hidden = jnp.take_along_axis(hidden, jnp.broadcast_to(idx, (B, 1, hidden.shape[2])), axis=1)
    logits = (hidden @ params["lm_head"].astype(hidden.dtype)).astype(jnp.float32)
    logits = logits / t.logits_scaling
    logits = constrain(logits, policy.logits)
    logits = sampling_ops.mask_padded_logits(logits, t.vocab_pad)
    if output_all_logits and gather_last_token:
        idx = batch["last_token_index"][:, None, None]
        last_logits = jnp.take_along_axis(
            logits, jnp.broadcast_to(idx, (B, 1, logits.shape[2])), axis=1)
    else:
        last_logits = logits

    outputs: Dict[str, jax.Array] = {}
    if attend_to_cache and input_ids.shape[1] == 1:
        # two scalars beside the tokens, in the same fetch (serving/engine.py
        # _collect_decode): blocks the sparse layers read and could have read,
        # summed over rows, KV heads and layers
        outputs["sparse_blocks_read"], outputs["sparse_blocks_live"] = counts
    if on_device_sampling:
        outputs["tokens"] = sampling_ops.sample(
            last_logits[:, -1, :], batch["sampling_params"], rng=batch.get("rng"),
            do_sample=do_sample, global_topk=global_topk, deterministic=deterministic,
        )[:, None]
    if output_logits or output_all_logits or not on_device_sampling:
        outputs["logits"] = logits[..., : t.vocab_size - t.vocab_pad]
    return outputs, new_cache


# ---------------------------------------------------------------------------
# Conversion / specs / structs
# ---------------------------------------------------------------------------


def _segments(config):
    """[(kind, n layers)] of the stacked params segments, in depth order."""
    out = []
    for kind in held_kinds(config):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1] + 1)
        else:
            out.append((kind, 1))
    return out


def _layer_struct(arch: SALAArch, kind: str, n: int, leaf):
    t = arch.sparse if kind == SPARSE else arch.lightning
    H, I = t.hidden_size, t.intermediate_size
    NH, NKV, D = t.num_attention_heads, t.num_kv_heads, t.head_dim
    attn = {
        "q_proj": {"w": leaf(n, H, NH * D)}, "k_proj": {"w": leaf(n, H, NKV * D)},
        "v_proj": {"w": leaf(n, H, NKV * D)}, "o_proj": {"w": leaf(n, NH * D, H)},
        "gate_proj": {"w": leaf(n, H, NH * D)}, "q_norm": leaf(n, D), "k_norm": leaf(n, D),
    }
    if kind == LIGHTNING:
        attn["o_norm"] = leaf(n, NH * D)  # one norm over all the heads' channels
    return {
        "input_layernorm": leaf(n, H), "post_attention_layernorm": leaf(n, H), "attn": attn,
        "mlp": {"gate_proj": {"w": leaf(n, H, I)}, "up_proj": {"w": leaf(n, H, I)},
                "down_proj": {"w": leaf(n, I, H)}},
    }


def param_shape_struct(config: InferenceConfig):
    arch = build_arch(config)
    dt = dense.np_dtype(arch.sparse.dtype)

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    H, V = arch.sparse.hidden_size, arch.sparse.vocab_size
    return {
        "embed_tokens": leaf(V, H),
        "segments": [_layer_struct(arch, kind, n, leaf) for kind, n in _segments(config)],
        "norm": leaf(H),
        "lm_head": leaf(H, V),
    }


def param_specs(config: InferenceConfig):
    from jax.sharding import PartitionSpec as P

    # tensor parallel is refused (build_arch): every leaf whole on its chip
    return jax.tree_util.tree_map(lambda s: P(*((None,) * len(s.shape))), param_shape_struct(config))


def convert_hf_state_dict(state_dict: Dict[str, np.ndarray], config: InferenceConfig):
    arch = build_arch(config)
    dt = dense.np_dtype(arch.sparse.dtype)
    first = config.first_hidden_layer

    def cast(x):
        return np.asarray(x, dt)

    def layer(i, kind):
        pre = f"model.layers.{first + i}."

        def get(name):
            for key in (pre + name, pre.replace("model.", "", 1) + name):
                if key in state_dict:
                    return state_dict[key]
            raise KeyError(pre + name)

        attn = {
            f"{p}_proj": {"w": cast(np.asarray(get(f"self_attn.{p}_proj.weight")).T)} for p in "qkvo"
        }
        attn["gate_proj"] = {"w": cast(np.asarray(get("self_attn.o_gate.weight")).T)}
        for name in ("q_norm", "k_norm") + (("o_norm",) if kind == LIGHTNING else ()):
            attn[name] = cast(get(f"self_attn.{name}.weight"))
        return {
            "input_layernorm": cast(get("input_layernorm.weight")),
            "post_attention_layernorm": cast(get("post_attention_layernorm.weight")),
            "attn": attn,
            "mlp": {f"{p}_proj": {"w": cast(np.asarray(get(f"mlp.{p}_proj.weight")).T)}
                    for p in ("gate", "up", "down")},
        }

    segments, i = [], 0
    for kind, n in _segments(config):
        segments.append(dense.tree_stack([layer(i + j, kind) for j in range(n)]))
        i += n

    def top(name):
        for key in (f"model.{name}", name):
            if key in state_dict:
                return state_dict[key]
        raise KeyError(name)

    return {
        "embed_tokens": cast(top("embed_tokens.weight")),
        "segments": segments,
        "norm": cast(top("norm.weight")),
        "lm_head": cast(np.asarray(state_dict["lm_head.weight"]).T),
    }


class MiniCPMSALAForCausalLM:
    def __new__(cls, *args, **kwargs):
        from nxdi_tpu.models.minicpm_sala.application import MiniCPMSALAApplication

        return MiniCPMSALAApplication(*args, **kwargs)


def __getattr__(name):
    # lazy APPLICATION_CLS: application.py imports this module
    if name == "APPLICATION_CLS":
        from nxdi_tpu.models.minicpm_sala.application import MiniCPMSALAApplication

        return MiniCPMSALAApplication
    raise AttributeError(name)
