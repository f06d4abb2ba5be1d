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

Under the block KV layout the two kinds of layer keep two kinds of cache: the
full layers' rows in the block pool (``k``/``v``: by block table, keys
zero-padded to whole lane tiles, one tile a pool row), the window layers' in ``k_swa``/``v_swa``, ring
rows a SLOT (``sliding_window`` rounded up to a lane tile), which no block
table addresses: a window layer never needs more, so it needs no allocator
and no release rule. ``causal_lm_forward`` walks the segments with each kind's
cache WHOLE (``run_decoder_layers(first_layer=)``).

HF weight layout: llama-style attention (+ ``self_attn.attention_sink_bias``);
router ``mlp.gate`` (+ ``e_score_correction_bias``); experts
``mlp.experts.{i}.gate/up/down_proj``; dense layers ``mlp.gate/up/down_proj``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from nxdi_tpu.config import InferenceConfig
from nxdi_tpu.models import dense
from nxdi_tpu.models.base import DecoderArch
from nxdi_tpu.ops.moe import (
    MoEArch, convert_hf_experts, moe_parallel_fields, moe_shape_struct,
)
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


@dataclass(frozen=True)
class MiMoV2Arch:
    """Two per-type decoder arches + the depth-ordered segment walk.

    Each schedule entry: (attn_type, type_lo, type_hi, seg_idx) — half-open
    type-local layer range into that type's stacked params/cache, and the
    index of the stacked params segment in ``params["segments"]``."""

    full: DecoderArch
    swa: DecoderArch
    schedule: Tuple[Tuple[str, int, int, int], ...]
    swa_theta: float

    #: under the block layout the window layers keep ring rows a SLOT beside
    #: the pool, in these cache leaves: their programs' batches carry the
    #: rows' slot ids (runtime/model_wrapper.py ``per_slot_cache``,
    #: serving/engine.py ``_layout_kwargs``) and every program takes the
    #: leaves in one memory layout (``_pinned_cache_layouts``)
    ring_cache_keys = ("k_swa", "v_swa")

    # the app sizes the FULL-type cache through the usual path
    def kv_cache_spec(self, batch_size, max_len, quant_dtype=None):
        return self.full.kv_cache_spec(batch_size, max_len, quant_dtype=quant_dtype)

    @property
    def num_layers(self):
        return self.full.num_layers + self.swa.num_layers

    @property
    def kv_window_pattern(self):
        """Depth-ordered window flags (schedule order) — lets the wrapper's
        layout selection keep the CONTIGUOUS layout primary under
        window_sized_kv (only the swa stack rides the ring; see
        application.py / kv_layout_from_config)."""
        flags = []
        for kind, lo, hi, _ in self.schedule:
            flags.extend([kind == "swa"] * (hi - lo))
        return tuple(flags)

    def __getattr__(self, name):
        # the runtime reads generic decoder attrs (vocab, dtype, sampler
        # wiring) — proxy them to the full-attention arch
        return getattr(object.__getattribute__(self, "full"), name)


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
        **moe_parallel_fields(config.tpu_config, E),
    )


def _layer_types(config) -> List[str]:
    return ["swa" if p == 1 else "full" for p in config.hybrid_layer_pattern]


def _layer_moe(config) -> List[bool]:
    return [bool(f) for f in config.moe_layer_freq]


def build_arch(config: InferenceConfig, **overrides) -> MiMoV2Arch:
    tp = config.tpu_config.tp_degree
    prf = float(config.partial_rotary_factor)
    types = _layer_types(config)
    moe = _moe_arch(config)

    def type_arch(kind: str) -> DecoderArch:
        if kind == "swa":
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
    uses_moe = _layer_moe(config)
    schedule = []
    counters = {"full": 0, "swa": 0}
    seg_idx = -1
    prev = None
    for i, kind in enumerate(types):
        key = (kind, uses_moe[i])
        lo = counters[kind]
        if key == prev:
            t, a, b, s = schedule[-1]
            schedule[-1] = (t, a, b + 1, s)
        else:
            seg_idx += 1
            schedule.append((kind, lo, lo + 1, seg_idx))
            prev = key
        counters[kind] += 1
    return MiMoV2Arch(
        full=type_arch("full"),
        swa=type_arch("swa"),
        schedule=tuple(schedule),
        swa_theta=float(getattr(config, "swa_rope_theta", 10000.0)),
    )


def build_inv_freq(config: InferenceConfig) -> Dict[str, np.ndarray]:
    prf = float(config.partial_rotary_factor)
    return {
        "full": inv_freq_from_hf_config(
            _rope_dim(config.head_dim, prf), config.rope_theta, None
        ),
        "swa": inv_freq_from_hf_config(
            _rope_dim(config.swa_head_dim, prf),
            getattr(config, "swa_rope_theta", 10000.0),
            None,
        ),
    }


# ---------------------------------------------------------------------------
# Forward — segment walk over two attention types
# ---------------------------------------------------------------------------


def causal_lm_forward(
    arch: MiMoV2Arch,
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
    import jax.numpy as jnp

    from nxdi_tpu.config import to_jax_dtype
    from nxdi_tpu.kvcache.kv_cache import DEFAULT_KV_LAYOUT, BlockKVLayout
    from nxdi_tpu.models.base import constrain
    from nxdi_tpu.ops import sampling as sampling_ops
    from nxdi_tpu.ops.norms import rms_norm
    from nxdi_tpu.ops.rope import rope_cos_sin
    from nxdi_tpu.parallel.policy import DEFAULT_POLICY

    policy = policy or DEFAULT_POLICY
    layout = layout or DEFAULT_KV_LAYOUT
    t = arch.full
    compute_dtype = to_jax_dtype(t.dtype)
    input_ids = batch["input_ids"]
    position_ids = batch["position_ids"]
    B = input_ids.shape[0]

    hidden = jnp.take(params["embed_tokens"], input_ids, axis=0).astype(compute_dtype)
    hidden = constrain(hidden, policy.hidden)
    cos_full, sin_full = rope_cos_sin(position_ids, np.asarray(inv_freq["full"]))
    cos_swa, sin_swa = rope_cos_sin(position_ids, np.asarray(inv_freq["swa"]))

    from nxdi_tpu.models.base import collect_cache_inputs

    # full layout-input pass-through: seq_ids (continuous batching; under the
    # block layout the rows' SLOT ids, which address the window stack's store),
    # write_positions (spec verify windows), attn_mask, last_token_index
    # (the ring write's keep-mask under right padding — WindowKVLayout.update)
    cache_inputs = collect_cache_inputs(batch) or None
    rope = {"full": (cos_full, sin_full), "swa": (cos_swa, sin_swa)}
    held_tally = None
    if isinstance(layout, BlockKVLayout):
        if t.moe.held_experts is not None and attend_to_cache and input_ids.shape[1] == 1:
            held_tally = []  # as models/base.py causal_lm_forward counts a share
        hidden, new_cache = _walk_paged(
            arch, params, hidden, rope, cache, position_ids, attend_to_cache,
            policy, layout, cache_inputs, held_tally,
        )
    else:
        hidden, new_cache = _walk_contiguous(
            arch, params, hidden, rope, cache, position_ids, attend_to_cache,
            kv_window, policy, layout, cache_inputs,
        )

    hidden = rms_norm(hidden, params["norm"], t.rms_norm_eps)
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = jnp.swapaxes(params["embed_tokens"], 0, 1)
    if gather_last_token and not output_all_logits:
        idx = batch["last_token_index"][:, None, None]
        hidden = jnp.take_along_axis(
            hidden, jnp.broadcast_to(idx, (B, 1, hidden.shape[2])), axis=1
        )
    logits = (hidden @ lm_head.astype(hidden.dtype)).astype(jnp.float32)
    logits = constrain(logits, policy.logits)
    logits = sampling_ops.mask_padded_logits(logits, t.vocab_pad)

    if output_all_logits and gather_last_token:
        # ungathered hidden: the sampler still needs the TRUE last position,
        # not the bucket-padded tail (base.py:1464-1469)
        idx = batch["last_token_index"][:, None, None]
        last_logits = jnp.take_along_axis(
            logits, jnp.broadcast_to(idx, (B, 1, logits.shape[2])), axis=1
        )
    else:
        last_logits = logits

    outputs: Dict[str, jax.Array] = {}
    if held_tally:
        # two scalars beside the tokens (serving/engine.py _collect_decode):
        # one pair a segment, summed over the walk
        outputs["moe_held_pairs"], outputs["moe_routed_layers"] = sum(held_tally[1:], held_tally[0])
    if on_device_sampling:
        outputs["tokens"] = sampling_ops.sample(
            last_logits[:, -1, :],
            batch["sampling_params"],
            rng=batch.get("rng"),
            do_sample=do_sample,
            global_topk=global_topk,
            deterministic=deterministic,
        )[:, None]
    if output_logits or output_all_logits or not on_device_sampling:
        outputs["logits"] = logits[..., : t.vocab_size - t.vocab_pad]
    return outputs, new_cache


def _walk_contiguous(
    arch, params, hidden, rope, cache, position_ids, attend_to_cache,
    kv_window, policy, layout, cache_inputs,
):
    """The contiguous layout's walk: each segment scans its slice of its
    kind's (L, B, KV, S, D) stack, and the slices are stacked back."""
    import jax.numpy as jnp

    from nxdi_tpu.models.base import run_decoder_layers

    caches = {
        "full": (cache["k"], cache["v"]),
        "swa": (cache["k_swa"], cache["v_swa"]),
    }
    # window-sized swa stack: when the swa cache holds fewer slots than the
    # full stack it is a W-slot ring — swa segments then read/write through
    # the ring layout (reference: per-layer window-sized caches,
    # kv_cache_manager.py:195-210); the full stack keeps the primary layout
    layouts = {"full": layout, "swa": layout}
    if cache["k_swa"].shape[3] < cache["k"].shape[3]:
        from nxdi_tpu.kvcache.kv_cache import WindowKVLayout

        layouts["swa"] = WindowKVLayout(
            window=cache["k_swa"].shape[3],
            route_by_seq_id=getattr(layout, "route_by_seq_id", False),
        )
    seg_new = {"full": {}, "swa": {}}  # type -> {lo: (k, v)}
    for kind, lo, hi, seg_idx in arch.schedule:
        ta = arch.full if kind == "full" else arch.swa
        ck, cv = caches[kind]
        k_sl = jax.lax.slice_in_dim(ck, lo, hi, axis=0)
        v_sl = jax.lax.slice_in_dim(cv, lo, hi, axis=0)
        spec = ta.kv_cache_spec(ck.shape[1], ck.shape[3])
        with jax.named_scope(_SCOPE[kind]):
            hidden, seg_cache = run_decoder_layers(
                ta, params["segments"][seg_idx], hidden, *rope[kind],
                {"k": k_sl, "v": v_sl}, position_ids, spec, attend_to_cache,
                kv_window=kv_window, policy=policy, layout=layouts[kind],
                cache_inputs=cache_inputs,
            )
        seg_new[kind][lo] = seg_cache

    def rebuild(kind):
        parts = [seg_new[kind][lo] for lo in sorted(seg_new[kind])]
        if not parts:
            return caches[kind]
        cat = lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)  # noqa: E731
        return cat([p["k"] for p in parts]), cat([p["v"] for p in parts])

    new_cache = {}
    new_cache["k"], new_cache["v"] = rebuild("full")
    new_cache["k_swa"], new_cache["v_swa"] = rebuild("swa")
    return hidden, new_cache


def _walk_paged(
    arch, params, hidden, rope, cache, position_ids, attend_to_cache,
    policy, layout, cache_inputs, held_tally,
):
    """The block layout's walk. Each kind's cache goes WHOLE into every
    segment's ``run_decoder_layers`` with the segment's first type-local layer
    (``first_layer``): no slice and no stacking of either.

    The full layers' pool is the layer scan's carry, written at (layer, slot)
    and read through the block table. The window layers' store holds ``R``
    ring rows a slot, position ``p`` in row ``p % R`` of the request's SLOT
    (``seq_ids``): a decode step attends the store in place plus its fresh
    row, and the five layers' fresh rows are committed by ONE in-place call
    after the walk (``WindowKVLayout.commit_rows``: the commit kernel); a
    prefill attends its fresh rows alone and its last ``min(S, R)`` rows go
    into the slot's rows by one ``dynamic_update_slice`` a batch row."""
    import jax.numpy as jnp

    from nxdi_tpu.kvcache.kv_cache import (
        DEFAULT_KV_LAYOUT, BlockKVCacheSpec, WindowKVLayout,
    )
    from nxdi_tpu.models.base import run_decoder_layers

    if cache_inputs is None or "seq_ids" not in cache_inputs:
        raise ValueError(
            "the window layers' store is addressed by the rows' slot ids: the "
            "batch needs seq_ids beside block_table / slot_mapping"
        )
    full, swa = arch.full, arch.swa
    pool = {"k": cache["k"], "v": cache["v"]}
    store = {"k": cache["k_swa"], "v": cache["v_swa"]}
    pool_spec = BlockKVCacheSpec(
        num_layers=full.num_layers,
        num_blocks=pool["k"].shape[1] // layout.block_size,
        block_size=layout.block_size,
        num_kv_heads=pool["k"].shape[2],
        head_dim=pool["k"].shape[3],
        dtype=full.dtype,
        v_head_dim=pool["v"].shape[3],
        key_tiles=layout.key_tiles(pool["k"], pool["v"]),
    )
    slots, R = store["k"].shape[1], store["k"].shape[3]
    ring = WindowKVLayout(window=R, route_by_seq_id=True)
    ring_spec = swa.kv_cache_spec(slots, R)
    B, S = position_ids.shape
    rows = {}  # first type-local layer -> the segment's fresh (n, B, KV, S, D) rows
    for kind, lo, hi, seg_idx in arch.schedule:
        seg = params["segments"][seg_idx]
        with jax.named_scope(_SCOPE[kind]):
            if kind == "full":
                hidden, pool = run_decoder_layers(
                    full, seg, hidden, *rope[kind], pool, position_ids, pool_spec,
                    attend_to_cache, policy=policy, layout=layout,
                    cache_inputs=cache_inputs, moe_held_tally=held_tally, first_layer=lo,
                )
                # a one-layer segment is no loop: without this the compiler is
                # free to recompute the segment's write on the pool it was
                # handed instead of keeping the written one, and then holds
                # two pools (3.4 GiB of ``temp`` at the served sizes)
                hidden, pool = jax.lax.optimization_barrier((hidden, pool))
            elif attend_to_cache:
                hidden, out = run_decoder_layers(
                    swa, seg, hidden, *rope[kind], store, position_ids, ring_spec,
                    True, policy=policy, layout=ring, cache_inputs=cache_inputs,
                    moe_held_tally=held_tally, first_layer=lo,
                )
                rows[lo] = (out["k_rows"], out["v_rows"])
            else:
                # a prefill reads no cache: it runs over a scratch of its own
                # rows (the contiguous layout's write at the origin IS the
                # fresh rows), and the store gets their tail below
                spec = swa.kv_cache_spec(B, S)
                scratch = {
                    "k": jnp.zeros((hi - lo,) + spec.shape[1:], spec.store_dtype),
                    "v": jnp.zeros((hi - lo,) + spec.shape_v[1:], spec.store_dtype),
                }
                hidden, out = run_decoder_layers(
                    swa, seg, hidden, *rope[kind], scratch, position_ids, spec,
                    False, policy=policy, layout=DEFAULT_KV_LAYOUT,
                )
                rows[lo] = (out["k"], out["v"])
    if rows:
        k_rows = jnp.concatenate([rows[lo][0] for lo in sorted(rows)], axis=0)
        v_rows = jnp.concatenate([rows[lo][1] for lo in sorted(rows)], axis=0)
        ci = dict(cache_inputs, position_ids=position_ids)
        with jax.named_scope("kv.write"):
            if attend_to_cache:
                store = ring.commit_rows(store, k_rows, v_rows, ci, ring_spec, policy=policy)
            else:
                store = _commit_prompt_tail(store, k_rows, v_rows, ci)
    return hidden, {
        "k": pool["k"], "v": pool["v"], "k_swa": store["k"], "v_swa": store["v"],
    }


def _commit_prompt_tail(store, k_rows, v_rows, ci):
    """A fresh prefill's rows (L, B, KV, S, D) into the slots' ring rows: row
    ``s`` of a slot gets the LAST position ``p <= last`` with ``p % R == s``
    (``last``: the row's last real token; bucket padding never lands), one
    in-place ``dynamic_update_slice`` of (L, 1, KV, R, D) a batch row. Rows no
    position has reached yet keep whatever they held: the ring's read places
    them at negative positions and hides them (``WindowKVLayout.read``)."""
    import jax.numpy as jnp

    R = store["k"].shape[3]
    pos = ci["position_ids"].astype(jnp.int32)  # (B, S), an arange from its first
    lti = ci.get("last_token_index")
    last = pos[:, -1] if lti is None else jnp.take_along_axis(
        pos, lti[:, None].astype(jnp.int32), axis=1)[:, 0]
    s = jnp.arange(R, dtype=jnp.int32)[None, :]
    src = last[:, None] - ((last[:, None] - s) % R) - pos[:, :1]  # (B, R) index into S
    src = jnp.clip(src, 0, pos.shape[1] - 1)
    slot_ids = ci["seq_ids"].astype(jnp.int32)
    out = {}
    for name, fresh in (("k", k_rows), ("v", v_rows)):
        arr = store[name]
        tail = jnp.take_along_axis(
            fresh, src[None, :, None, :, None], axis=3
        ).astype(arr.dtype)  # (L, B, KV, R, D)
        for b in range(tail.shape[1]):
            arr = jax.lax.dynamic_update_slice(
                arr, tail[:, b : b + 1], (0, slot_ids[b], 0, 0, 0)
            )
        out[name] = arr
    return out


#: ``jax.named_scope`` of the two kinds of segment
_SCOPE = {"full": "layers.full", "swa": "layers.window"}


# ---------------------------------------------------------------------------
# Conversion / specs / structs
# ---------------------------------------------------------------------------


def _convert_layer(state_dict, config, arch: MiMoV2Arch, i: int, kind: str, use_moe: bool):
    ta = arch.full if kind == "full" else arch.swa
    tp = config.tpu_config.tp_degree
    if kind == "swa":
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
    types = _layer_types(config)
    uses_moe = _layer_moe(config)
    dt = dense.np_dtype(arch.full.dtype)

    # group depth-contiguous layers into the schedule's segments
    segments: List[Any] = []
    bucket: List[Any] = []
    prev = None
    for i, kind in enumerate(types):
        key = (kind, uses_moe[i])
        if prev is not None and key != prev:
            segments.append(dense.tree_stack(bucket))
            bucket = []
        bucket.append(_convert_layer(state_dict, config, arch, i, kind, uses_moe[i]))
        prev = key
    segments.append(dense.tree_stack(bucket))
    assert len(segments) == len({s for (_, _, _, s) in arch.schedule})

    def top(name):
        for k in (f"model.{name}", name):
            if k in state_dict:
                return state_dict[k]
        raise KeyError(name)

    embed = np.asarray(top("embed_tokens.weight"))
    if arch.full.vocab_pad:
        embed = np.concatenate(
            [embed, np.zeros((arch.full.vocab_pad, embed.shape[1]), embed.dtype)]
        )
    params: Dict[str, Any] = {
        "embed_tokens": np.asarray(embed, dt),
        "segments": segments,
        "norm": np.asarray(top("norm.weight"), dt),
    }
    if not arch.full.tie_word_embeddings:
        head = np.asarray(state_dict["lm_head.weight"])
        if arch.full.vocab_pad:
            head = np.concatenate(
                [head, np.zeros((arch.full.vocab_pad, head.shape[1]), head.dtype)]
            )
        params["lm_head"] = np.asarray(head.T, dt)
    return params


def _map_segments(config, per_layer_fn, top_fn):
    """Build the segments-list structure by mapping a per-layer constructor."""
    arch = build_arch(config)
    types = _layer_types(config)
    uses_moe = _layer_moe(config)
    segs, bucket, prev = [], [], None
    for i, kind in enumerate(types):
        key = (kind, uses_moe[i])
        if prev is not None and key != prev:
            segs.append(bucket)
            bucket = []
        bucket.append(per_layer_fn(arch, kind, uses_moe[i]))
        prev = key
    segs.append(bucket)
    return top_fn(arch, segs)


def param_specs(config: InferenceConfig):
    from jax.sharding import PartitionSpec as P

    from nxdi_tpu.models.base import attention_param_specs, mlp_param_specs
    from nxdi_tpu.ops.moe import expert_parallel_specs
    from nxdi_tpu.parallel.layers import REPLICATED, VOCAB_PARALLEL

    def per_layer(arch, kind, use_moe):
        ta = arch.full if kind == "full" else arch.swa
        layer = {
            "input_layernorm": REPLICATED,
            "post_attention_layernorm": REPLICATED,
            "attn": attention_param_specs(ta),
        }
        if ta.attention_sink:
            layer["attn"]["sink"] = REPLICATED
        if use_moe:
            layer["moe"] = expert_parallel_specs(ta.moe)
        else:
            layer["mlp"] = mlp_param_specs(ta)
        return layer

    def top(arch, segs):
        def stack(tree):
            return jax.tree_util.tree_map(
                lambda sp: P(*((None,) + tuple(sp))),
                tree,
                is_leaf=lambda x: isinstance(x, P),
            )

        specs = {
            "embed_tokens": VOCAB_PARALLEL,
            "segments": [stack(s[0]) for s in segs],
            "norm": REPLICATED,
        }
        if not arch.full.tie_word_embeddings:
            from nxdi_tpu.parallel.layers import COLUMN_PARALLEL

            specs["lm_head"] = COLUMN_PARALLEL
        return specs

    return _map_segments(config, per_layer, top)


def param_shape_struct(config: InferenceConfig):
    arch = build_arch(config)
    types = _layer_types(config)
    uses_moe = _layer_moe(config)
    dt = dense.np_dtype(arch.full.dtype)
    H = arch.full.hidden_size

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    def layer_struct(kind, use_moe, n):
        ta = arch.full if kind == "full" else arch.swa
        D, Dv = ta.head_dim, ta.v_head_dim or ta.head_dim
        NH, NKV = ta.num_attention_heads, ta.num_kv_heads
        layer = {
            "input_layernorm": s(n, H),
            "post_attention_layernorm": s(n, H),
            "attn": {
                "q_proj": {"w": s(n, H, NH * D)},
                "k_proj": {"w": s(n, H, NKV * D)},
                "v_proj": {"w": s(n, H, NKV * Dv)},
                "o_proj": {"w": s(n, NH * Dv, H)},
            },
        }
        if ta.attention_sink:
            layer["attn"]["sink"] = s(n, NH)
        if use_moe:
            # the router scores all experts, the tree holds this chip's
            layer["moe"] = moe_shape_struct(ta.moe, H, n, dt)
        else:
            I = config.intermediate_size
            layer["mlp"] = {
                "gate_proj": {"w": s(n, H, I)},
                "up_proj": {"w": s(n, H, I)},
                "down_proj": {"w": s(n, I, H)},
            }
        return layer

    segs, run, prev = [], 0, None
    order = []
    for i, kind in enumerate(types):
        key = (kind, uses_moe[i])
        if prev is not None and key != prev:
            order.append((prev, run))
            run = 0
        run += 1
        prev = key
    order.append((prev, run))
    for (kind, use_moe), n in order:
        segs.append(layer_struct(kind, use_moe, n))

    V = arch.full.vocab_size
    struct = {
        "embed_tokens": s(V, H),
        "segments": segs,
        "norm": s(H),
    }
    if not arch.full.tie_word_embeddings:
        struct["lm_head"] = s(H, V)
    return struct


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
