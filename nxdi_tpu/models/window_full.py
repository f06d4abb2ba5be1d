"""The walk of a decoder whose layers are of two kinds, FULL attention and
sliding-WINDOW attention, interleaved in depth: each kind has a
:class:`DecoderArch` of its own (head counts, widths, rope, norms), a layer
stack of params of its own and a cache of its own. MiMo-V2-Flash
(``models/mimo_v2``) and K-EXAONE (``models/exaone_moe``) are served by it; a
family gives its two arches, its rope tables and its checkpoint's names.

The params are a LIST of layer-stacked segments, one a depth-contiguous run of
layers of one kind and one feed-forward (dense or routed). The schedule walks
them in depth order: ``(kind, lo, hi, segment)``, ``[lo, hi)`` the kind's own
layer indices, into that kind's stacked cache.

Under the block KV layout the two kinds keep two kinds of cache: the full
layers' rows in the block pool (``k``/``v``: by block table, keys zero-padded
to whole lane tiles, one tile a pool row), the window layers' in
``k_swa``/``v_swa``, ring rows a SLOT (``sliding_window`` rounded up to a lane
tile), which no block table addresses: a window layer never needs more, so it
needs no allocator and no release rule. ``causal_lm_forward`` walks the
segments with each kind's cache WHOLE (``run_decoder_layers(first_layer=)``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import numpy as np

from nxdi_tpu.kvcache.kv_cache import (
    block_kv_cache_partition_spec,
    kv_cache_partition_spec,
)
from nxdi_tpu.models import dense
from nxdi_tpu.models.base import DecoderArch
from nxdi_tpu.runtime.application import TpuModelForCausalLM

FULL, WINDOW = "full", "swa"

#: lane tile of the TPU: a cache row is kept at a multiple of it where a
#: kernel's own block copies read it
LANES = 128


@dataclass(frozen=True)
class WindowFullArch:
    """Two per-kind decoder arches + the depth-ordered segment walk.

    Each schedule entry: (kind, type_lo, type_hi, seg_idx) — half-open
    kind-local layer range into that kind's stacked params/cache, and the
    index of the stacked params segment in ``params["segments"]``;
    ``routed[seg_idx]`` says whether the segment's feed-forward is the
    experts."""

    full: DecoderArch
    swa: DecoderArch
    schedule: Tuple[Tuple[str, int, int, int], ...]
    routed: Tuple[bool, ...]

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
        ``WindowFullApplication`` / kv_layout_from_config)."""
        flags = []
        for kind, lo, hi, _ in self.schedule:
            flags.extend([kind == WINDOW] * (hi - lo))
        return tuple(flags)

    def of(self, kind: str) -> DecoderArch:
        return self.full if kind == FULL else self.swa

    def __getattr__(self, name):
        # the runtime reads generic decoder attrs (vocab, dtype, sampler
        # wiring) — proxy them to the full-attention arch
        return getattr(object.__getattribute__(self, "full"), name)


def segment_runs(kinds: Sequence[str], routed: Sequence[bool]) -> List[Tuple[str, bool, int]]:
    """``(kind, routed, layers)`` of each depth-contiguous run of layers of one
    kind and one feed-forward, in depth order: the params' segments."""
    runs: List[Tuple[str, bool, int]] = []
    for kind, r in zip(kinds, routed):
        if runs and runs[-1][:2] == (kind, r):
            runs[-1] = (kind, r, runs[-1][2] + 1)
        else:
            runs.append((kind, r, 1))
    return runs


def build(full: DecoderArch, swa: DecoderArch, kinds: Sequence[str], routed: Sequence[bool]) -> WindowFullArch:
    """The arch of a model whose layer ``i`` is of kind ``kinds[i]`` (FULL or
    WINDOW) with a routed feed-forward where ``routed[i]``."""
    schedule, counters = [], {FULL: 0, WINDOW: 0}
    runs = segment_runs(kinds, routed)
    for seg_idx, (kind, _, n) in enumerate(runs):
        lo = counters[kind]
        schedule.append((kind, lo, lo + n, seg_idx))
        counters[kind] = lo + n
    return WindowFullArch(
        full=full, swa=swa, schedule=tuple(schedule), routed=tuple(r for _, r, _ in runs),
    )


# ---------------------------------------------------------------------------
# Forward — segment walk over two attention kinds
# ---------------------------------------------------------------------------


def causal_lm_forward(
    arch: WindowFullArch,
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
    """``inv_freq``: ``{FULL: table, WINDOW: table}``, each kind's rope
    frequencies (a kind whose arch has ``no_rope`` reads none of its table)."""
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

    with jax.named_scope("embed"):
        hidden = jnp.take(params["embed_tokens"], input_ids, axis=0).astype(compute_dtype)
        hidden = constrain(hidden, policy.hidden)
    rope = {kind: rope_cos_sin(position_ids, np.asarray(inv_freq[kind])) for kind in (FULL, WINDOW)}

    from nxdi_tpu.models.base import collect_cache_inputs, counts_held_pairs, held_pair_outputs

    # full layout-input pass-through: seq_ids (continuous batching; under the
    # block layout the rows' SLOT ids, which address the window stack's store),
    # write_positions (spec verify windows), attn_mask, last_token_index
    # (the ring write's keep-mask under right padding — WindowKVLayout.update)
    cache_inputs = collect_cache_inputs(batch) or None
    held_tally = None
    if isinstance(layout, BlockKVLayout):
        if counts_held_pairs(t.moe, input_ids.shape, attend_to_cache, layout):
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

    with jax.named_scope("final_norm"):
        hidden = rms_norm(hidden, params["norm"], t.rms_norm_eps)
    with jax.named_scope("lm_head"):
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
        # two scalars beside the tokens (serving/engine.py _collect_decode,
        # _prefill_chunk): one pair a segment, summed over the walk
        outputs.update(held_pair_outputs(held_tally, prefill=input_ids.shape[1] > 1))
    if on_device_sampling:
        with jax.named_scope("sample"):
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
        FULL: (cache["k"], cache["v"]),
        WINDOW: (cache["k_swa"], cache["v_swa"]),
    }
    # window-sized swa stack: when the swa cache holds fewer slots than the
    # full stack it is a W-slot ring — swa segments then read/write through
    # the ring layout (reference: per-layer window-sized caches,
    # kv_cache_manager.py:195-210); the full stack keeps the primary layout
    layouts = {FULL: layout, WINDOW: layout}
    if cache["k_swa"].shape[3] < cache["k"].shape[3]:
        from nxdi_tpu.kvcache.kv_cache import WindowKVLayout

        layouts[WINDOW] = WindowKVLayout(
            window=cache["k_swa"].shape[3],
            route_by_seq_id=getattr(layout, "route_by_seq_id", False),
        )
    seg_new = {FULL: {}, WINDOW: {}}  # kind -> {lo: (k, v)}
    for kind, lo, hi, seg_idx in arch.schedule:
        ta = arch.of(kind)
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
    new_cache["k"], new_cache["v"] = rebuild(FULL)
    new_cache["k_swa"], new_cache["v_swa"] = rebuild(WINDOW)
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
    row, and the window layers' fresh rows are committed by ONE in-place call
    after the walk (``WindowKVLayout.commit_rows``: the commit kernel); a
    prefill attends its fresh rows alone and its last ``min(S, R)`` rows go
    into the slot's rows by one ``dynamic_update_slice`` a batch row. The
    commit is the window layers' own, under their container scope."""
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
            if kind == FULL:
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
                    # the rows behind a prompt's last token: the expert layer's
                    # compact form leaves them out (ops/moe.py)
                    cache_inputs={k: v for k, v in cache_inputs.items() if k == "last_token_index"},
                    moe_held_tally=held_tally,
                )
                rows[lo] = (out["k"], out["v"])
    if rows:
        k_rows = jnp.concatenate([rows[lo][0] for lo in sorted(rows)], axis=0)
        v_rows = jnp.concatenate([rows[lo][1] for lo in sorted(rows)], axis=0)
        ci = dict(cache_inputs, position_ids=position_ids)
        with jax.named_scope(_SCOPE[WINDOW]), jax.named_scope("kv.write"):
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
_SCOPE = {FULL: "layers.full", WINDOW: "layers.window"}


# ---------------------------------------------------------------------------
# Params: specs, shapes, and the checkpoint's segments
# ---------------------------------------------------------------------------


def _layer_specs(ta: DecoderArch, routed: bool):
    from nxdi_tpu.models.base import attention_param_specs, mlp_param_specs
    from nxdi_tpu.ops.moe import expert_parallel_specs
    from nxdi_tpu.parallel.layers import REPLICATED

    layer = {
        "input_layernorm": REPLICATED,
        "post_attention_layernorm": REPLICATED,
        "attn": attention_param_specs(ta),
    }
    if ta.attention_sink:
        layer["attn"]["sink"] = REPLICATED
    if routed:
        layer["moe"] = expert_parallel_specs(ta.moe)
    else:
        layer["mlp"] = mlp_param_specs(ta)
    return layer


def param_specs(arch: WindowFullArch):
    from jax.sharding import PartitionSpec as P

    from nxdi_tpu.parallel.layers import COLUMN_PARALLEL, REPLICATED, VOCAB_PARALLEL

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda sp: P(*((None,) + tuple(sp))), tree, is_leaf=lambda x: isinstance(x, P),
        )

    specs = {
        "embed_tokens": VOCAB_PARALLEL,
        "segments": [stack(_layer_specs(arch.of(kind), arch.routed[seg]))
                     for kind, _, _, seg in arch.schedule],
        "norm": REPLICATED,
    }
    if not arch.full.tie_word_embeddings:
        specs["lm_head"] = COLUMN_PARALLEL
    return specs


def param_shape_struct(arch: WindowFullArch):
    from nxdi_tpu.ops.moe import moe_shape_struct

    dt = dense.np_dtype(arch.full.dtype)
    H = arch.full.hidden_size

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    def layer_struct(ta: DecoderArch, routed: bool, n: int):
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
        if ta.qk_norm:  # one weight a head's width, each of q and k
            layer["attn"]["q_norm"] = s(n, D)
            layer["attn"]["k_norm"] = s(n, D)
        if ta.attention_sink:
            layer["attn"]["sink"] = s(n, NH)
        if routed:
            # the router scores all experts, the tree holds this chip's
            layer["moe"] = moe_shape_struct(ta.moe, H, n, dt)
        else:
            I = ta.intermediate_size
            layer["mlp"] = {
                "gate_proj": {"w": s(n, H, I)},
                "up_proj": {"w": s(n, H, I)},
                "down_proj": {"w": s(n, I, H)},
            }
        return layer

    V = arch.full.vocab_size
    struct = {
        "embed_tokens": s(V, H),
        "segments": [layer_struct(arch.of(kind), arch.routed[seg], hi - lo)
                     for kind, lo, hi, seg in arch.schedule],
        "norm": s(H),
    }
    if not arch.full.tie_word_embeddings:
        struct["lm_head"] = s(H, V)
    return struct


def convert_segments(
    state_dict: Dict[str, np.ndarray], arch: WindowFullArch,
    convert_layer: Callable[[int, str, bool], Dict[str, Any]],
) -> Dict[str, Any]:
    """The params from a checkpoint: ``convert_layer(depth, kind, routed)``
    gives one layer's tree (the family knows its checkpoint's names); the
    layers are stacked into the schedule's segments, and the embedding, the
    final norm and the head (each padded to the arch's vocabulary) beside."""
    dt = dense.np_dtype(arch.full.dtype)
    segments, depth = [], 0
    for kind, lo, hi, seg in arch.schedule:
        n = hi - lo
        segments.append(dense.tree_stack(
            [convert_layer(depth + j, kind, arch.routed[seg]) for j in range(n)]))
        depth += n

    def top(name):
        for k in (f"model.{name}", name):
            if k in state_dict:
                return state_dict[k]
        raise KeyError(name)

    def padded(table):
        table = np.asarray(table)
        if arch.full.vocab_pad:
            table = np.concatenate([table, np.zeros((arch.full.vocab_pad, table.shape[1]), table.dtype)])
        return table

    params: Dict[str, Any] = {
        "embed_tokens": np.asarray(padded(top("embed_tokens.weight")), dt),
        "segments": segments,
        "norm": np.asarray(top("norm.weight"), dt),
    }
    if not arch.full.tie_word_embeddings:
        params["lm_head"] = np.asarray(padded(state_dict["lm_head.weight"]).T, dt)
    return params


# ---------------------------------------------------------------------------
# The application: a cache tree of two kinds
# ---------------------------------------------------------------------------


def _round_up(n: int, tile: int) -> int:
    return -(-n // tile) * tile


class WindowFullApplication(TpuModelForCausalLM):
    """The application of a window/full family: the FULL layers' cache through
    the usual path (under the block layout the pool, by block table: the
    engine's ``BlockSpaceManager``, unchanged) and, beside it, the WINDOW
    layers' ``k_swa``/``v_swa`` (``WindowFullArch.ring_cache_keys``): ring rows
    a SLOT under the block layout, addressed by the rows' slot ids, which the
    engine hands the programs beside the block tables. A window layer never
    attends more than ``sliding_window`` rows, so its share of the cache is a
    constant a slot: no allocator, no release rule, no second table. What
    would have to snapshot or hand over a slot's ring rows is refused by name.

    A family names itself (``NAME``); its module's ``build_arch`` gives a
    :class:`WindowFullArch`, which every program walks by
    :func:`causal_lm_forward`."""

    #: the family's name, as its refusals give it
    NAME = ""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        tc = self.tpu_config
        paged = tc.is_block_kv_layout
        for flag, why in (
            (tc.async_mode, "async (device-resident) decode"),
            (tc.lora_config is not None, "LoRA serving"),
            (tc.enable_fused_speculation or tc.is_medusa,
             "fused/medusa speculative decoding"),
            (getattr(tc, "pp_degree", 1) > 1, "pipeline parallel"),
            # a cache-attending prefill would have to read the window layers'
            # ring rows as well as the pool
            (tc.is_prefix_caching or tc.is_chunked_prefill, "prefix/chunked prefill"),
            (paged and tc.mixed_dispatch, "mixed dispatch over the paged layout"),
            # the hand-off plane exports block chains; a slot's ring rows are not one
            (paged and tc.role != "unified", "prefill/decode hand-off of the window store"),
            (paged and tc.kv_quant_config is not None, "a quantized paged cache"),
        ):
            if flag:
                raise NotImplementedError(f"{self.NAME} does not support {why} yet")

    def _interleaved_window_split(self, arch=None, family=None, config=None):
        return None  # the family manages its own two stacks (k_swa/v_swa)

    def _cache_spec(self, family=None, config=None):
        # the FULL-attention stack always keeps seq_len slots; window_sized_kv
        # shrinks only the swa stack (see _swa_cache_struct)
        arch = self.family.build_arch(self.config)
        tc = self.tpu_config
        if tc.is_block_kv_layout:
            from nxdi_tpu.kvcache.kv_cache import BlockKVCacheSpec

            full = arch.full
            # the full layers' pool: a key row wider than a lane tile is kept
            # zero-padded as whole tiles, one a pool row (a 192-wide row lies
            # in 256 lanes of HBM either way; kvcache BlockKVLayout, KEY
            # TILES); values at their own width
            tiles = _round_up(full.head_dim, LANES) // LANES if full.head_dim > LANES else 1
            return BlockKVCacheSpec(
                num_layers=full.num_layers,
                num_blocks=tc.pa_num_blocks,
                block_size=tc.pa_block_size,
                num_kv_heads=full.num_kv_heads,
                head_dim=LANES if tiles > 1 else full.head_dim,
                dtype=full.dtype,
                v_head_dim=full.v_head_dim,
                key_tiles=tiles,
            )
        return arch.kv_cache_spec(
            tc.kv_cache_batch_size + tc.kv_cache_padding_size,
            tc.seq_len,
            quant_dtype=(tc.kv_quant_config.dtype if tc.kv_quant_config else None),
        )

    def _swa_cache_struct(self):
        arch = self.family.build_arch(self.config)
        tc = self.tpu_config
        B = tc.kv_cache_batch_size + tc.kv_cache_padding_size
        # window_sized_kv shrinks ONLY the sliding-window stack to a W-slot
        # ring; full-attention layers keep the seq_len stack (reference:
        # per-layer window-sized cache shapes, kv_cache_manager.py:195-210)
        max_len = tc.seq_len
        if tc.is_block_kv_layout:
            # ring rows a slot: the window, rounded up to a tile (the commit
            # kernel writes lane-aligned windows of rows). A decode step
            # attends the old rows plus its fresh one, so the window's own
            # count of rows is enough
            w = arch.swa.sliding_window
            max_len = min(max_len, _round_up(w, LANES if w >= LANES else 8))
        elif getattr(tc, "window_sized_kv", False):
            # window_ring_slots over-provisions by spec_len+1 under linear
            # speculation so rejected-draft writes never clobber live rows
            max_len = min(max_len, tc.window_ring_slots)
        spec = arch.swa.kv_cache_spec(
            B, max_len,
            quant_dtype=(tc.kv_quant_config.dtype if tc.kv_quant_config else None),
        )
        return {
            "k_swa": jax.ShapeDtypeStruct(spec.shape, spec.store_dtype),
            "v_swa": jax.ShapeDtypeStruct(spec.shape_v, spec.store_dtype),
        }

    def _cache_struct(self):
        struct = super()._cache_struct()
        struct.update(self._swa_cache_struct())
        return struct

    def init_cache_host(self):
        import jax.numpy as jnp

        cache = super().init_cache_host()
        for k, s in self._swa_cache_struct().items():
            cache[k] = jnp.zeros(s.shape, s.dtype)
        return cache

    def cache_partition_specs(self):
        ring = kv_cache_partition_spec(self.tpu_config)  # (L, slots, KV, rows, D)
        specs = dict(
            block_kv_cache_partition_spec() if self.tpu_config.is_block_kv_layout else ring
        )
        specs["k_swa"], specs["v_swa"] = ring["k"], ring["v"]
        return specs

    def enable_models(self) -> None:
        super().enable_models()
        for w in self.models.values():
            w.forward_fn = causal_lm_forward
            w.forward_kwargs.pop("tensor_capture", None)
            w.forward_kwargs.pop("return_next_inputs", None)
            if w.forward_kwargs.pop("dp_sampling", False):
                raise NotImplementedError(
                    f"{self.NAME} does not support dp_sampling yet"
                )
