"""Which attention computes a call: ONE ordered table, asked by every call site.

``attention_block`` (models/base.py), ``mla_attention_block`` (ops/mla.py) and
``run_decoder_layers`` describe what they can observe statically as a
:class:`Site` and ask :func:`select` (the stack also :func:`defers`). The
answer is the FIRST row of :data:`TABLE` that serves the site's phase, layout,
write mode and attention form, computes every mask term the site needs, and
whose kernel flag, shape predicate and sharding predicate hold. A selected
call runs: what a ``sharded_*_call`` cannot take is its row's ``sharding``
predicate. Where no row computes the site's terms, :func:`select` raises
instead of compiling a mask without them.

The choice is static (flags, shapes, mesh layout), so recording the chosen name
at trace time is exact; ``runtime/model_wrapper.py`` snapshots the trace per
(submodel, bucket) and ``benchmark/configs/*.json`` name the same strings: the
names are an interface. The projection kernels (``mlp_block``, fused qkv)
choose for themselves and record here; which of their flags a program MUST
have engaged is the wrapper's (``ModelWrapper._required_strategies``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax

from nxdi_tpu.kvcache.kv_cache import BlockKVLayout, WindowKVLayout
from nxdi_tpu.ops import kernels
from nxdi_tpu.ops.kernels import mla_decode

_STRATEGY_TRACE: list = []


def _record_strategy(name: str) -> None:
    _STRATEGY_TRACE.append(name)


#: the mask terms a call may need beyond causal-by-position
TERMS = frozenset({
    "window", "chunk", "sink", "softcap",  # the architecture's
    "window_flag", "rope_flag",  # per-layer scan flags gating window / chunk
    "bidir", "attn_mask", "write_positions",  # the call's cache inputs
    "v_width",  # values of another width than the keys (arch.v_head_dim)
    # the call reads a SELECTION of blocks (ops/block_select.py; its cache
    # inputs carry it: a decode step's compact table, a prefill's block mask)
    "block_select",
})
ATTN, TKG, BLOCK_TKG = ATTENTION_FLAGS = (
    "attn_kernel_enabled", "attn_tkg_kernel_enabled", "attn_block_tkg_kernel_enabled"
)


@dataclass(frozen=True)
class Site:
    """What one attention call can observe before it computes anything."""

    phase: str  # fresh | prefill_cached | decode | mixed | spec_window | mask_override
    layout: str  # contiguous | block | window (ring)
    deferred: bool  # the write is left to the stack's one commit after the scan
    needs: frozenset  # of TERMS
    flags: frozenset  # the enabled ones of ATTENTION_FLAGS
    q_shape: Tuple[int, ...]  # (B, H, S, D)
    kv_shape: Optional[Tuple[int, ...]]  # (B, KV, W, D) attended: fresh rows or the read window
    policy: object  # parallel/policy.py ShardingPolicy
    meshed: bool  # a mesh is in context (without one nothing is sharded)
    mla: Optional[str] = None  # latent attention: "expanded" | "absorbed"
    pool_shape: Optional[Tuple[int, ...]] = None  # block layout: (L, slots, KV, D)
    v_pool_shape: Optional[Tuple[int, ...]] = None  # absorbed form: the latent pool
    block_size: Optional[int] = None
    stacked: bool = False  # the whole (L, B, KV, S, D) stack is at hand as a kernel operand
    raw_cache: bool = False  # cache rows are compute dtype, unscaled, not routed by seq id


@dataclass(frozen=True)
class Row:
    name: str
    phases: Tuple[str, ...]
    computes: frozenset = frozenset()  # the TERMS this strategy's mask and output honour
    layouts: Optional[Tuple[str, ...]] = None  # None: any
    deferred: bool = False
    forms: Tuple[Optional[str], ...] = (None,)  # Site.mla values served
    flag: Optional[str] = None  # the arch flag that admits it (None: always there)
    shape: Optional[Callable[[Site], bool]] = None
    sharding: Optional[Callable[[Site], bool]] = None


def _kv_seq_local(s: Site) -> bool:
    """The flat kernels run per shard: a KV sequence split over chips (flash
    decoding) needs a cross-shard softmax they do not have."""
    spec = s.policy.kv if s.phase == "fresh" else s.policy.cache_kv
    return not s.meshed or spec[2] is None


def _rows_local(s: Site) -> bool:
    """The paged kernels walk whole rows of the block table: batch or query
    sequence split over chips (DP / CP / flash decoding) is not theirs."""
    return not s.meshed or (s.policy.q[0] is None and s.policy.q[2] is None)


def _flat(supported):  # a ``*_kernel_supported(q_shape, k_shape)`` over what is attended
    return lambda s: supported(s.q_shape, s.kv_shape)


def _pool(supported):  # a ``*_kernel_supported(q_shape, pool_shape, block_size)``
    return lambda s: supported(s.q_shape, s.pool_shape, s.block_size)


def _paged_decode(s: Site) -> bool:  # both pools' widths: the values may have their own
    return kernels.paged_decode_kernel_supported(
        s.q_shape, s.pool_shape, s.block_size, s.v_pool_shape
    )


ATTENDING = ("decode", "prefill_cached")
# ops/attention.py's position masks take the others
_XLA = TERMS - {"attn_mask", "bidir", "block_select"}
_STATIC = frozenset({"window", "chunk"})  # the flat kernels take a window and a chunk, no flag
_WRITTEN = frozenset({"write_positions"})  # the row is in the cache before the core reads it
_PAGED = dict(layouts=("block",), sharding=_rows_local)  # causal by position and no more

TABLE: Tuple[Row, ...] = (
    # the draft window's scratch IS the write target: always a deferred call
    Row("tkg_spec_window_xla", ("spec_window",), _XLA - _WRITTEN, deferred=True),
    # reads the old cache from the raw stack by layer index: nothing may sit
    # between the stored rows and the kernel; asked per STACK before any mesh
    # is looked at, so its sharding term is the policy's alone
    Row("tkg_fused_kernel_stacked", ("decode",), _STATIC, ("contiguous",), True, flag=TKG,
        shape=lambda s: s.stacked and s.raw_cache
        and kernels.fused_decode_kernel_supported(s.q_shape, s.kv_shape),
        sharding=lambda s: s.policy.cache_kv[2] is None),
    # contiguous only: a ring's kv positions wrap
    Row("tkg_fused_kernel", ("decode",), _STATIC, ("contiguous",), True, flag=TKG,
        shape=_flat(kernels.fused_decode_kernel_supported), sharding=_kv_seq_local),
    Row("tkg_two_part_xla", ATTENDING, _XLA, ("contiguous", "window"), True),
    Row("mixed_ragged_kernel", ("mixed",), flag=ATTN, **_PAGED,
        shape=_pool(kernels.ragged_paged_kernel_supported)),
    # the mask rebuilt from the token tags is causal within a row and no more:
    # a window, chunk, sink or softcap is refused here, not dropped
    Row("mixed_ragged_xla", ("mixed",), frozenset({"v_width"}), ("block",)),
    Row("cte_paged_kernel", ("prefill_cached",), flag=ATTN, **_PAGED,
        shape=_pool(kernels.paged_prefill_kernel_supported)),
    # the one paged kernel that takes values of their own width; a selection
    # reaches it as the table it walks (holes are skipped without a copy)
    Row("tkg_paged_kernel", ("decode",), _WRITTEN | {"v_width", "block_select"}, flag=BLOCK_TKG,
        **_PAGED,
        shape=_paged_decode),
    # the caller's mask IS the mask (tree verification): applications reject
    # window / chunk architectures with it up front; sink and softcap apply
    Row("attn_mask_override_xla", ("mask_override",), TERMS - {"bidir", "block_select"}),
    Row("tkg_kernel", ("decode",), _STATIC | _WRITTEN, flag=TKG,
        shape=_flat(kernels.decode_kernel_supported), sharding=_kv_seq_local),
    Row("tkg_xla", ATTENDING, _XLA),
    # latent attention pads its values to the key width; its one-token fresh
    # call stays in XLA. The prefill kernel takes values of their own width
    # and a learned sink (the flat decode kernel, ``tkg_kernel``, neither)
    # ... and a block selection as one more operand (a block mask a query token)
    Row("cte_flash_kernel", ("fresh",), _STATIC | _WRITTEN | {"v_width", "sink", "block_select"},
        forms=(None, "expanded"), flag=ATTN,
        shape=lambda s: kernels.prefill_kernel_supported(s.q_shape, s.kv_shape)
        and (s.mla is None or s.q_shape[2] > 1),
        sharding=_kv_seq_local),
    Row("cte_xla", ("fresh",), TERMS - {"attn_mask", "block_select"}, forms=(None, "expanded")),
    Row("tkg_mla_paged_kernel", ("decode",), _WRITTEN, forms=("absorbed",), flag=BLOCK_TKG,
        **_PAGED, shape=lambda s: s.v_pool_shape is not None
        and mla_decode.mla_paged_decode_supported(
            s.q_shape[:2] + s.v_pool_shape[-1:], s.pool_shape, s.v_pool_shape, s.block_size)),
    Row("tkg_mla_paged_xla", ("decode",), _WRITTEN, ("block",), forms=("absorbed",)),
    Row("tkg_mla_xla", ATTENDING, _WRITTEN, forms=("expanded",)),
)


#: what the user can do about a term no row computes for the call
REMEDIES = {
    "block_select": "a block selection is computed by the paged decode kernel and the flash "
                    "prefill kernel alone (attn_block_tkg_kernel_enabled / attn_kernel_enabled): "
                    "a dense pass in its place would be another model",
    # span ids restart per chunk: same-image tokens of the cached prefix could never match
    "bidir": "bidirectional image attention (gemma3-vision) does not compose with "
             "prefix-cached/chunked prefill; disable prefix caching for this model",
}


def _serves(row: Row, s: Site) -> bool:
    return (
        s.phase in row.phases
        and (row.layouts is None or s.layout in row.layouts)
        and row.deferred == s.deferred
        and s.mla in row.forms
        and s.needs <= row.computes
    )


def select(s: Site, record: bool = True) -> str:
    """The first row's name, recorded once (``record=False``: only asked)."""
    for row in TABLE:
        if (
            _serves(row, s)
            and (row.flag is None or row.flag in s.flags)
            and (row.shape is None or row.shape(s))
            and (row.sharding is None or row.sharding(s))
        ):
            if record:
                _record_strategy(row.name)
            return row.name
    raise NotImplementedError(
        f"no attention strategy computes {sorted(s.needs) or 'a causal mask'} for a "
        f"{s.phase} call over the {s.layout} layout (deferred write: {s.deferred}, "
        f"latent form: {s.mla}); rows: ops/attention_select.py TABLE"
        + "".join(f"; {REMEDIES[t]}" for t in sorted(s.needs) if t in REMEDIES)
    )


def defers(s: Site) -> bool:
    """Whether the layer stack leaves this call's write to ONE commit after the
    scan: its layout commits rows (a paged pool and a whole-stack ring write
    per layer; the ring layers of an interleaved stack follow their stack) and
    some deferred row serves the call."""
    return s.layout == "contiguous" and any(r.deferred and _serves(r, s) for r in TABLE)


def site_of(
    arch, layout, policy, cache_inputs, q_shape, k_shape, k_cache, compute_dtype,
    *, attend_to_cache: bool, deferred: bool = False, layer_flags=(False, False),
    stacked: bool = False, spec_window: bool = False, v_cache=None,
) -> Site:
    """The :class:`Site` of one call. ``k_cache`` (shape and dtype) is the
    layer's cache view, or the whole pool under the block layout; ``q_shape``
    (B, H, S, D) and ``k_shape`` are the fresh rows'; ``layer_flags`` says
    whether per-layer (window, rope) flags ride the scan."""
    ci = cache_inputs or {}
    S = q_shape[2]
    block = isinstance(layout, BlockKVLayout)
    kind = "block" if block else "window" if isinstance(layout, WindowKVLayout) else "contiguous"
    needed = {
        "window": arch.sliding_window is not None,
        "chunk": arch.chunk_size is not None,
        "sink": bool(arch.attention_sink),
        "softcap": arch.attn_logit_softcap is not None,
        "window_flag": layer_flags[0],
        "rope_flag": layer_flags[1],
        "bidir": ci.get("bidir_spans") is not None and S > 1,
        "attn_mask": ci.get("attn_mask") is not None,
        "write_positions": ci.get("write_positions") is not None,
        "v_width": arch.v_head_dim is not None,
        "block_select": ci.get("block_select") is not None,
    }
    needs = frozenset(term for term, is_needed in needed.items() if is_needed)
    if not attend_to_cache:
        phase = "fresh"
    elif spec_window:
        phase = "spec_window"
    elif ci.get("mixed_row_ids") is not None and S > 1:
        phase = "mixed"
    elif "attn_mask" in needs:
        phase = "mask_override"
    else:
        phase = "decode" if S == 1 else "prefill_cached"
    form = None
    if arch.mla is not None:
        form = "absorbed" if block and phase == "decode" and "block_table" in ci else "expanded"
        if needs - {"write_positions"}:  # ops/mla.py builds the causal mask and no other
            raise NotImplementedError(
                f"latent attention computes no {sorted(needs - {'write_positions'})} term"
            )
    kv_shape = k_shape
    if attend_to_cache and block and "block_table" in ci:
        width = ci["block_table"].shape[-1] * layout.block_size
        kv_shape = (q_shape[0], k_cache.shape[2], width, q_shape[3])  # as ``layout.read`` hands it
    elif attend_to_cache and not block:
        kv_shape = tuple(k_cache.shape)
    mesh = jax.sharding.get_abstract_mesh()
    return Site(
        phase=phase, layout=kind, deferred=deferred, needs=needs,
        flags=frozenset(f for f in ATTENTION_FLAGS if getattr(arch, f)),
        q_shape=tuple(q_shape), kv_shape=kv_shape, policy=policy,
        meshed=not (mesh is None or mesh.empty), mla=form,
        pool_shape=tuple(k_cache.shape) if block else None,
        v_pool_shape=tuple(v_cache.shape) if v_cache is not None else None,
        block_size=layout.block_size if block else None, stacked=stacked,
        raw_cache=(
            not getattr(layout, "route_by_seq_id", False)
            and getattr(layout, "k_scale", 1.0) == 1.0
            and getattr(layout, "v_scale", 1.0) == 1.0
            and not getattr(layout, "has_array_scales", lambda: False)()
            and k_cache.dtype == compute_dtype
        ),
    )
