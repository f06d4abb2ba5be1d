"""The attention table (nxdi_tpu/ops/attention_select.py): which strategy each
call site is answered, and what each kernel row falls to when a single term
it does not compute is present.

Every case traces ONE attention block from shapes alone (``jax.eval_shape``:
no model, no compile) and reads the recorded strategy. The expected names
were pinned by running this file's harness over the PARENT's
``attention_block`` / ``mla_attention_block`` (commit f1e11af, its ladder of
guards) once: ``python tests/unit/test_attention_select.py <parent root>``
prints them. Where the table answers otherwise than the parent did, the case
says so.
"""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

B, H, KV, D, HID = 2, 4, 2, 16, 32
CACHE_LEN, BLOCK, BLOCKS, LAYERS = 32, 8, 8, 2
F32, I32 = jnp.float32, jnp.int32
RAISES = "NotImplementedError"


def _s(shape, dtype=F32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def trace_of():
    """The strategy trace of whichever tree is on the path."""
    try:
        from nxdi_tpu.ops import attention_select as owner
    except ImportError:  # the parent: the trace lived in models/base.py
        from nxdi_tpu.models import base as owner
    return owner._STRATEGY_TRACE


def call(
    *, layout="contiguous", attend=True, S=1, flags=(), defer=False, stacked=False, spec=False,
    mixed=False, ci=(), layer_flags=(), arch=None, mla=False, head_dim=D, mosaic=False,
    sharded=None, swallow=True,
):
    """Trace one attention block described by the keywords; return the ONE
    strategy it recorded, or ``RAISES`` (``swallow=False``: let it raise).
    ``sharded``: trace under a two-chip mesh with a policy that splits the
    "kv_seq" (flash decoding) or the "rows" (data parallel) over it."""
    from nxdi_tpu.kvcache.kv_cache import (
        BlockKVCacheSpec, BlockKVLayout, ContiguousKVLayout, KVCacheSpec, WindowKVLayout,
    )
    from nxdi_tpu.models import base
    from nxdi_tpu.ops.kernels import mode
    from nxdi_tpu.parallel.policy import ShardingPolicy

    arch = dict(arch or {})
    d = head_dim
    dv = arch.get("v_head_dim") or d
    b = 1 if mixed else B
    mla_arch = None
    if mla:
        from nxdi_tpu.ops.mla import MLAArch

        mla_arch = MLAArch(num_heads=H, q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=8,
                           qk_rope_head_dim=8, v_head_dim=8, softmax_scale=0.25)
    a = base.DecoderArch(
        num_layers=LAYERS, hidden_size=HID, num_attention_heads=H, num_kv_heads=KV, head_dim=d,
        intermediate_size=64, vocab_size=64, vocab_pad=0, dtype="float32", mla=mla_arch,
        **{f: True for f in flags}, **arch,
    )
    kd, vd, kvh = (8, 32, 1) if mla else (d, dv, KV)  # a latent cache: rope key, latent
    if layout == "block":
        lay = BlockKVLayout(block_size=BLOCK)
        spec_ = BlockKVCacheSpec(num_layers=LAYERS, num_blocks=BLOCKS, block_size=BLOCK,
                                 num_kv_heads=kvh, head_dim=kd, dtype="float32")
        k_cache, v_cache = _s((LAYERS, BLOCKS * BLOCK, kvh, kd)), _s((LAYERS, BLOCKS * BLOCK, kvh, vd))
    else:
        lay = WindowKVLayout(window=CACHE_LEN) if layout == "window" else ContiguousKVLayout()
        spec_ = KVCacheSpec(num_layers=LAYERS, batch_size=b, num_kv_heads=kvh, max_len=CACHE_LEN,
                            head_dim=kd, dtype="float32")
        k_cache, v_cache = _s((b, kvh, CACHE_LEN, kd)), _s((b, kvh, CACHE_LEN, vd))
    inputs = {}
    if layout == "block":
        inputs["slot_mapping"] = _s((b, S), I32)
        inputs["block_table"] = _s((b, 2 * 2 if mixed else 2), I32)
    if layout == "window":
        inputs["last_token_index"] = _s((b,), I32)
    if mixed:
        inputs["mixed_row_ids"] = _s((1, S), I32)
        inputs["last_token_index"] = _s((2,), I32)  # two rows packed into the stream
    extra = {"attn_mask": _s((b, S, CACHE_LEN), jnp.bool_), "write_positions": _s((b, S), I32),
             "bidir_spans": _s((b, S), I32),
             # a block selection: a fresh call's block mask, a decode step's own table
             "block_select": _s((b, 2), I32) if attend else _s((b, KV, S, max(S // BLOCK, 1)), jnp.bool_)}
    inputs.update({k: extra[k] for k in ci})
    if mla:
        p_attn = {"q_proj": {"w": _s((HID, H * 16))}, "kv_a": {"w": _s((HID, 32 + 8))},
                  "kv_a_norm": _s((32,)), "kv_b": {"w": _s((32, H * 16))},
                  "o_proj": {"w": _s((H * 8, HID))}}
        cos = _s((b, S, 8))
    else:
        p_attn = {"q_proj": {"w": _s((HID, H * d))}, "k_proj": {"w": _s((HID, KV * d))},
                  "v_proj": {"w": _s((HID, KV * dv))}, "o_proj": {"w": _s((H * dv, HID))}}
        if a.attention_sink:
            p_attn["sink"] = _s((H,))
        cos = _s((b, S, d))
    kv_seq, by_row = P(None, None, "tp", None), P("dp", None, None, None)
    policy = {None: ShardingPolicy(), "kv_seq": ShardingPolicy(kv=kv_seq, cache_kv=kv_seq),
              "rows": ShardingPolicy(q=by_row, kv=by_row, cache_kv=by_row)}[sharded]
    scan_flags = {f: _s((), jnp.bool_) for f in layer_flags}
    operands = dict(p_attn=p_attn, hidden=_s((b, S, HID)), cos=cos, k_cache=k_cache,
                    v_cache=v_cache, pos=_s((b, S), I32), inputs=inputs, flags=scan_flags,
                    layer=_s((), I32))
    if stacked:
        operands["stack"] = (_s((LAYERS,) + k_cache.shape), _s((LAYERS,) + v_cache.shape))
    if spec:
        operands["window"] = (_s((b, KV, 4, d)), _s((b, KV, 4, dv)), _s((b, 4), I32), _s((), I32))

    def fn(o):
        common = (a, o["p_attn"], o["hidden"], o["cos"], o["cos"], o["k_cache"], o["v_cache"],
                  o["pos"], spec_, attend, policy, lay, o["inputs"], None,
                  o["flags"].get("use_sliding_window"), o["flags"].get("use_rope"))
        if mla:
            from nxdi_tpu.ops.mla import mla_attention_block

            return mla_attention_block(*common, layer_idx=o["layer"])[0]
        return base.attention_block(
            *common, defer_write=defer, layer_idx=o["layer"], stacked_layer_idx=o["layer"],
            tkg_stacked=o["stack"] + (None,) if stacked else None,
            spec_window=o["window"] if spec else None,
        )[0]

    trace = trace_of()
    trace.clear()
    interpret = mode.interpret
    if mosaic:  # the kernels' shape predicates as the chip's compiler sets them
        mode.interpret = lambda: False
    try:
        if sharded:
            devices = np.array(jax.devices()[:2]).reshape(2, 1, 1, 1)
            with jax.set_mesh(Mesh(devices, ("dp", "ep", "epx", "tp"))):
                jax.eval_shape(fn, operands)
        else:
            jax.eval_shape(fn, operands)
    except NotImplementedError:
        if not swallow:
            raise
        return RAISES
    finally:
        mode.interpret = interpret
    (name,) = trace
    return name


TKG, BLK, ATT = "attn_tkg_kernel_enabled", "attn_block_tkg_kernel_enabled", "attn_kernel_enabled"
DEFERRED = dict(defer=True, flags=(TKG,))
PAGED_DECODE = dict(layout="block", flags=(BLK, TKG))  # the flat decode kernel is its next row
PAGED_PREFILL = dict(layout="block", S=8, flags=(ATT,))
MIXED = dict(layout="block", S=8, mixed=True, flags=(ATT,))
FRESH = dict(attend=False, S=8, flags=(ATT,))
FLAT = dict(flags=(TKG,))
WINDOW, CHUNK = dict(sliding_window=8), dict(chunk_size=8)
SINK, SOFTCAP, V_WIDTH = dict(attention_sink=True), dict(attn_logit_softcap=30.0), dict(v_head_dim=8)
KV_SEQ_SHARDED, ROWS_SHARDED = dict(sharded="kv_seq"), dict(sharded="rows")

CASES = {
    # -- PR 37: a block selection reaches the two kernels that read one
    "block-select-fresh": (dict(FRESH, ci=("block_select",)), "cte_flash_kernel"),
    "block-select-paged-decode": (dict(PAGED_DECODE, ci=("block_select",)), "tkg_paged_kernel"),
    # -- each of the sixteen names reached
    "spec-window": (dict(spec=True, defer=True), "tkg_spec_window_xla"),
    "stacked": (dict(DEFERRED, stacked=True), "tkg_fused_kernel_stacked"),
    "fused": (DEFERRED, "tkg_fused_kernel"),
    "two-part": (dict(defer=True), "tkg_two_part_xla"),
    "two-part-verify-window": (dict(DEFERRED, S=4), "tkg_two_part_xla"),
    "ragged": (MIXED, "mixed_ragged_kernel"),
    "ragged-xla": (dict(MIXED, flags=()), "mixed_ragged_xla"),
    "paged-prefill": (PAGED_PREFILL, "cte_paged_kernel"),
    "paged-decode": (PAGED_DECODE, "tkg_paged_kernel"),
    "mask-override": (dict(S=4, ci=("attn_mask",), flags=(TKG,)), "attn_mask_override_xla"),
    "flat-decode": (FLAT, "tkg_kernel"),
    "flat-decode-over-gathered-blocks": (dict(layout="block", flags=(TKG,)), "tkg_kernel"),
    "flat-decode-over-a-ring": (dict(layout="window", flags=(TKG,)), "tkg_kernel"),
    "cached-xla": (dict(), "tkg_xla"),
    "cached-prefill-xla": (dict(S=8, flags=(TKG,)), "tkg_xla"),
    "flash": (FRESH, "cte_flash_kernel"),
    "flash-one-token": (dict(FRESH, S=1), "cte_flash_kernel"),
    "fresh-xla": (dict(FRESH, flags=()), "cte_xla"),
    "latent-paged": (dict(PAGED_DECODE, mla=True), "tkg_mla_paged_kernel"),
    "latent-paged-xla": (dict(layout="block", mla=True), "tkg_mla_paged_xla"),
    "latent-paged-prefill": (dict(PAGED_PREFILL, mla=True), "tkg_mla_xla"),
    "latent-cached": (dict(mla=True, flags=(TKG,)), "tkg_mla_xla"),
    "latent-flash": (dict(FRESH, mla=True), "cte_flash_kernel"),
    "latent-fresh-xla": (dict(FRESH, mla=True, flags=()), "cte_xla"),
    "latent-fresh-one-token": (dict(FRESH, mla=True, S=1), "cte_xla"),
    # -- the deferred kernels take the static window and chunk, nothing else
    "stacked+window": (dict(DEFERRED, stacked=True, arch=WINDOW), "tkg_fused_kernel_stacked"),
    "stacked+chunk": (dict(DEFERRED, stacked=True, arch=CHUNK), "tkg_fused_kernel_stacked"),
    "stacked-window-flag": (dict(DEFERRED, stacked=True, layer_flags=("use_sliding_window",)), "tkg_two_part_xla"),
    "stacked-rope-flag": (dict(DEFERRED, stacked=True, layer_flags=("use_rope",)), "tkg_two_part_xla"),
    "stacked-write-positions": (dict(DEFERRED, stacked=True, ci=("write_positions",)), "tkg_two_part_xla"),
    "fused+window": (dict(DEFERRED, arch=WINDOW), "tkg_fused_kernel"),
    "fused+chunk": (dict(DEFERRED, arch=CHUNK), "tkg_fused_kernel"),
    "fused-sink": (dict(DEFERRED, arch=SINK), "tkg_two_part_xla"),
    "fused-softcap": (dict(DEFERRED, arch=SOFTCAP), "tkg_two_part_xla"),
    "fused-v-width": (dict(DEFERRED, arch=V_WIDTH), "tkg_two_part_xla"),
    "fused-window-flag": (dict(DEFERRED, layer_flags=("use_sliding_window",)), "tkg_two_part_xla"),
    "fused-rope-flag": (dict(DEFERRED, layer_flags=("use_rope",)), "tkg_two_part_xla"),
    "fused-write-positions": (dict(DEFERRED, ci=("write_positions",)), "tkg_two_part_xla"),
    "fused-ring": (dict(DEFERRED, layout="window"), "tkg_two_part_xla"),
    "fused-shape": (dict(DEFERRED, mosaic=True), "tkg_two_part_xla"),
    "fused-kv-seq-sharded": (dict(DEFERRED, **KV_SEQ_SHARDED), "tkg_two_part_xla"),
    # -- the paged kernels mask causally by position and no more
    "paged-decode-window": (dict(PAGED_DECODE, arch=WINDOW), "tkg_kernel"),
    "paged-decode-chunk": (dict(PAGED_DECODE, arch=CHUNK), "tkg_kernel"),
    "paged-decode-sink": (dict(PAGED_DECODE, arch=SINK), "tkg_xla"),
    "paged-decode-softcap": (dict(PAGED_DECODE, arch=SOFTCAP), "tkg_xla"),
    "paged-decode-v-width": (dict(PAGED_DECODE, arch=V_WIDTH), "tkg_paged_kernel"),  # PR 35: the value pool's own width
    "paged-decode-v-width-shape": (dict(PAGED_DECODE, arch=V_WIDTH, mosaic=True, head_dim=12), "tkg_xla"),
    "paged-decode-v-width-sink": (dict(PAGED_DECODE, arch=dict(V_WIDTH, **SINK)), "tkg_xla"),
    "paged-decode-v-width-window": (dict(PAGED_DECODE, arch=dict(V_WIDTH, **WINDOW)), "tkg_xla"),
    "paged-decode-window-flag": (dict(PAGED_DECODE, layer_flags=("use_sliding_window",)), "tkg_xla"),
    "paged-decode-rope-flag": (dict(PAGED_DECODE, layer_flags=("use_rope",)), "tkg_xla"),
    "paged-decode-attn-mask": (dict(PAGED_DECODE, ci=("attn_mask",)), "attn_mask_override_xla"),
    "paged-decode+write-positions": (dict(PAGED_DECODE, ci=("write_positions",)), "tkg_paged_kernel"),
    "paged-decode-flag-off": (dict(layout="block", flags=(TKG, ATT)), "tkg_kernel"),
    "paged-decode-shape": (dict(PAGED_DECODE, mosaic=True, head_dim=12), "tkg_xla"),
    "paged-decode-rows-sharded": (dict(PAGED_DECODE, **ROWS_SHARDED), "tkg_kernel"),
    "paged-prefill-window": (dict(PAGED_PREFILL, arch=WINDOW), "tkg_xla"),
    "paged-prefill-chunk": (dict(PAGED_PREFILL, arch=CHUNK), "tkg_xla"),
    "paged-prefill-sink": (dict(PAGED_PREFILL, arch=SINK), "tkg_xla"),
    "paged-prefill-softcap": (dict(PAGED_PREFILL, arch=SOFTCAP), "tkg_xla"),
    "paged-prefill-v-width": (dict(PAGED_PREFILL, arch=V_WIDTH), "tkg_xla"),
    "paged-prefill-window-flag": (dict(PAGED_PREFILL, layer_flags=("use_sliding_window",)), "tkg_xla"),
    "paged-prefill-rope-flag": (dict(PAGED_PREFILL, layer_flags=("use_rope",)), "tkg_xla"),
    "paged-prefill-attn-mask": (dict(PAGED_PREFILL, ci=("attn_mask",)), "attn_mask_override_xla"),
    "paged-prefill-write-positions": (dict(PAGED_PREFILL, ci=("write_positions",)), "tkg_xla"),
    "paged-prefill-flag-off": (dict(PAGED_PREFILL, flags=(BLK, TKG)), "tkg_xla"),
    "paged-prefill-shape": (dict(PAGED_PREFILL, mosaic=True), "tkg_xla"),
    "paged-prefill-rows-sharded": (dict(PAGED_PREFILL, **ROWS_SHARDED), "tkg_xla"),
    "latent-paged-shape": (dict(PAGED_DECODE, mla=True, mosaic=True), "tkg_mla_paged_xla"),
    "latent-paged-rows-sharded": (dict(PAGED_DECODE, mla=True, **ROWS_SHARDED), "tkg_mla_paged_xla"),
    # -- the packed stream: the kernel's XLA form is causal within a row and no more
    "ragged-v-width": (dict(MIXED, arch=V_WIDTH), "mixed_ragged_xla"),
    "ragged-shape": (dict(MIXED, mosaic=True), "mixed_ragged_xla"),
    "ragged-rows-sharded": (dict(MIXED, **ROWS_SHARDED), "mixed_ragged_xla"),
    # -- the flat kernels
    "flat-decode+window": (dict(FLAT, arch=WINDOW), "tkg_kernel"),
    "flat-decode+chunk": (dict(FLAT, arch=CHUNK), "tkg_kernel"),
    "flat-decode+write-positions": (dict(FLAT, ci=("write_positions",)), "tkg_kernel"),
    "flat-decode-sink": (dict(FLAT, arch=SINK), "tkg_xla"),
    "flat-decode-softcap": (dict(FLAT, arch=SOFTCAP), "tkg_xla"),
    "flat-decode-v-width": (dict(FLAT, arch=V_WIDTH), "tkg_xla"),
    "flat-decode-window-flag": (dict(FLAT, layer_flags=("use_sliding_window",)), "tkg_xla"),
    "flat-decode-rope-flag": (dict(FLAT, layer_flags=("use_rope",)), "tkg_xla"),
    "flat-decode-shape": (dict(FLAT, mosaic=True), "tkg_xla"),
    "flat-decode-kv-seq-sharded": (dict(FLAT, **KV_SEQ_SHARDED), "tkg_xla"),
    "flash+window": (dict(FRESH, arch=WINDOW), "cte_flash_kernel"),
    "flash+chunk": (dict(FRESH, arch=CHUNK), "cte_flash_kernel"),
    "flash+write-positions": (dict(FRESH, ci=("write_positions",)), "cte_flash_kernel"),
    "flash-sink": (dict(FRESH, arch=SINK), "cte_flash_kernel"),  # PR 35: the sink starts the running state
    "flash-sink+window+v-width": (dict(FRESH, arch=dict(SINK, **WINDOW, **V_WIDTH)), "cte_flash_kernel"),
    "flash-sink-softcap": (dict(FRESH, arch=dict(SINK, **SOFTCAP)), "cte_xla"),
    "flash-sink-flag-off": (dict(FRESH, flags=(), arch=SINK), "cte_xla"),
    "ring-decode-sink+window+v-width": (dict(defer=True, layout="window", arch=dict(SINK, **WINDOW, **V_WIDTH)),
                                        "tkg_two_part_xla"),
    "flash-softcap": (dict(FRESH, arch=SOFTCAP), "cte_xla"),
    "flash-v-width": (dict(FRESH, arch=V_WIDTH), "cte_flash_kernel"),  # PR 35: values of their own width
    "flash-window-flag": (dict(FRESH, layer_flags=("use_sliding_window",)), "cte_xla"),
    "flash-rope-flag": (dict(FRESH, layer_flags=("use_rope",)), "cte_xla"),
    "flash-bidirectional-spans": (dict(FRESH, ci=("bidir_spans",)), "cte_xla"),
    "flash-shape": (dict(FRESH, mosaic=True), "cte_xla"),
    "flash-kv-seq-sharded": (dict(FRESH, **KV_SEQ_SHARDED), "cte_xla"),
    # -- refused at trace time (the parent raised here too)
    "bidirectional-spans-over-a-cache": (dict(S=8, ci=("bidir_spans",)), RAISES),
}

#: what the table refuses at trace time and the parent compiled without the
#: term (every one in the safe direction; no tier-1 app reaches any):
#: case -> a pattern of the error
REFUSED_SINCE_THE_TABLE = {
    # ``mixed_ragged_xla``'s mask had none of these (ROADMAP M1's "silently wrong math")
    "ragged-window": (dict(MIXED, arch=WINDOW), "'window'"),
    "ragged-chunk": (dict(MIXED, arch=CHUNK), "'chunk'"),
    "ragged-sink": (dict(MIXED, arch=SINK), "'sink'"),
    "ragged-softcap": (dict(MIXED, arch=SOFTCAP), "'softcap'"),
    "ragged-xla-window": (dict(MIXED, flags=(), arch=WINDOW), "'window'"),
    "ragged-attn-mask": (dict(MIXED, ci=("attn_mask",)), "'attn_mask'"),
    "ragged-write-positions": (dict(MIXED, ci=("write_positions",)), "'write_positions'"),
    "ragged-window-flag": (dict(MIXED, layer_flags=("use_sliding_window",)), "'window_flag'"),
    # the draft window's scratch is the write target: the parent ignored both
    "spec-window-write-positions": (dict(spec=True, defer=True, ci=("write_positions",)), "'write_positions'"),
    "spec-window-not-deferred": (dict(spec=True), "spec_window call .*deferred write: False"),
    # the parent's fresh (CTE) exits never looked at a caller's mask
    "fresh-attn-mask": (dict(FRESH, ci=("attn_mask",)), "'attn_mask'"),
    "fresh-xla-attn-mask": (dict(FRESH, flags=(), ci=("attn_mask",)), "'attn_mask'"),
    # the parent's deferred exit ran the two-part path causally over the spans
    "deferred-bidirectional-spans": (dict(defer=True, S=8, ci=("bidir_spans",)), "'bidir'.*disable prefix caching"),
    # ``ops/mla.py`` builds the causal mask and no other: the parent dropped these
    "latent-window": (dict(mla=True, arch=WINDOW), "latent attention computes no .*'window'"),
    "latent-fresh-chunk": (dict(FRESH, mla=True, arch=CHUNK), "latent attention computes no .*'chunk'"),
    "latent-paged-softcap": (dict(PAGED_DECODE, mla=True, arch=SOFTCAP), "latent attention computes no .*'softcap'"),
    "latent-attn-mask": (dict(mla=True, S=4, ci=("attn_mask",)), "latent attention computes no .*'attn_mask'"),
    # PR 37: a block selection is the two kernels' alone; no XLA row stands in with a dense pass
    "block-select-fresh-no-kernel": (dict(attend=False, S=8, ci=("block_select",)), "'block_select'.*another model"),
    "block-select-paged-decode-no-kernel": (dict(layout="block", ci=("block_select",)), "'block_select'.*another model"),
    "block-select-deferred": (dict(DEFERRED, ci=("block_select",)), "'block_select'"),
    "block-select-paged-prefill": (dict(PAGED_PREFILL, ci=("block_select",)), "'block_select'"),
    "block-select-fresh-wrong-flag": (dict(attend=False, S=8, flags=(TKG,), ci=("block_select",)), "'block_select'"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_site_is_answered_as_the_parent_answered(case):
    kw, expected = CASES[case]
    assert call(**kw) == expected


@pytest.mark.parametrize("case", sorted(REFUSED_SINCE_THE_TABLE))
def test_no_row_computes_the_term_so_the_trace_raises(case):
    kw, pattern = REFUSED_SINCE_THE_TABLE[case]
    with pytest.raises(NotImplementedError, match=pattern):
        call(**kw, swallow=False)


def test_a_refused_span_mask_still_says_what_to_do():
    """The parent's one refusal (image spans over a cached prefix) keeps its remedy."""
    with pytest.raises(NotImplementedError, match="'bidir'.*disable prefix caching for this model"):
        call(S=8, ci=("bidir_spans",), swallow=False)


def _site(*, layout="contiguous", S=1, attend=True, mixed=False, mla=False, deferred=False,
          stacked=False, flags=(), window_flag=False, attn_mask=False, write_positions=False,
          cache_dtype=F32, **arch):
    """A Site through ``site_of`` from stand-ins: the table alone, no trace."""
    from types import SimpleNamespace

    from nxdi_tpu.kvcache.kv_cache import BlockKVLayout, ContiguousKVLayout, WindowKVLayout
    from nxdi_tpu.ops import attention_select
    from nxdi_tpu.parallel.policy import ShardingPolicy

    a = SimpleNamespace(sliding_window=None, chunk_size=None, attention_sink=False,
                        attn_logit_softcap=None, v_head_dim=None, mla=object() if mla else None,
                        **{f: f in flags for f in attention_select.ATTENTION_FLAGS})
    vars(a).update(arch)
    lay = {"contiguous": ContiguousKVLayout(), "window": WindowKVLayout(window=CACHE_LEN),
           "block": BlockKVLayout(block_size=BLOCK)}[layout]
    ci = {"block_table": _s((B, 2), I32)} if layout == "block" else {}
    if mixed:
        ci["mixed_row_ids"] = _s((1, S), I32)
    if attn_mask:
        ci["attn_mask"] = _s((B, S, CACHE_LEN))
    if write_positions:
        ci["write_positions"] = _s((B, S), I32)
    cache = _s((LAYERS, BLOCKS * BLOCK, KV, D) if layout == "block" else (B, KV, CACHE_LEN, D),
               cache_dtype)
    return attention_select.site_of(
        a, lay, ShardingPolicy(), ci, (B, H, S, D), (B, KV, S, D), cache, F32,
        attend_to_cache=attend, deferred=deferred, layer_flags=(window_flag, False),
        stacked=stacked,
    )


STACKS = {
    # what run_decoder_layers asks once per stack: (site, defers, stacked kernel)
    "contiguous-decode": (dict(flags=(TKG,)), True, True),
    "contiguous-decode-flag-off": (dict(), True, False),
    "contiguous-verify-window": (dict(S=4, flags=(TKG,)), True, False),
    "contiguous-quantized-store": (dict(flags=(TKG,), cache_dtype=jnp.float8_e4m3fn), True, False),
    "contiguous-sink": (dict(flags=(TKG,), attention_sink=True), True, False),
    "contiguous-layer-flags": (dict(flags=(TKG,), window_flag=True), True, False),
    "contiguous-write-positions": (dict(flags=(TKG,), write_positions=True), True, False),
    "tree-verify-mask": (dict(S=4, attn_mask=True, flags=(TKG,)), False, False),
    "prefill": (dict(attend=False, S=8, flags=(TKG,)), False, False),
    "paged-pool": (dict(layout="block", flags=(TKG,)), False, False),
    "whole-stack-ring": (dict(layout="window", flags=(TKG,)), False, False),
    "latent": (dict(mla=True, flags=(TKG,)), False, False),
}


@pytest.mark.parametrize("case", sorted(STACKS))
def test_the_stack_asks_the_table_for_its_write_policy(case):
    """``defer`` and ``use_stacked_tkg`` as the parent's two guards in
    ``run_decoder_layers`` computed them."""
    from nxdi_tpu.ops import attention_select

    kw, defers, stacked = STACKS[case]
    site = _site(deferred=True, stacked=True, **kw)
    assert attention_select.defers(site) is defers
    took = defers and attention_select.select(site, record=False) == "tkg_fused_kernel_stacked"
    assert took is stacked


def test_a_policy_alone_refuses_the_stacked_kernel():
    """The stack is asked before any mesh is looked at: a KV-sequence-sharded
    policy refuses the stacked row with or without a mesh; the per-layer
    kernels only under one."""
    from nxdi_tpu.ops import attention_select
    from nxdi_tpu.parallel.policy import ShardingPolicy

    flash_decoding = ShardingPolicy(cache_kv=P(None, None, "tp", None))
    site = dataclasses.replace(_site(deferred=True, stacked=True, flags=(TKG,)), policy=flash_decoding)
    assert not site.meshed and attention_select.select(site, record=False) == "tkg_fused_kernel"
    meshed = dataclasses.replace(site, meshed=True)
    assert attention_select.select(meshed, record=False) == "tkg_two_part_xla"


def test_one_row_a_name_and_every_row_reached():
    from nxdi_tpu.ops import attention_select

    names = [r.name for r in attention_select.TABLE]
    assert len(names) == len(set(names)) == 16
    assert {expected for _, expected in CASES.values()} - {RAISES} == set(names)
    for row in attention_select.TABLE:
        assert row.computes <= attention_select.TERMS, row.name
        assert row.flag is None or row.flag in attention_select.ATTENTION_FLAGS, row.name


@pytest.mark.parametrize("flags, expected", [
    ((), ()),
    (("mlp_kernel_enabled",), (("mlp_kernel_enabled", ("mlp_fused_kernel",)),)),
    (("fused_qkv",), (("fused_qkv", ("qkv_fused_matmul", "qkv_fused_kernel")),)),
    (("fused_qkv", "qkv_kernel_enabled"), (("qkv_kernel_enabled", ("qkv_fused_kernel",)),)),
    (("mlp_kernel_enabled", "qkv_kernel_enabled"),
     (("mlp_kernel_enabled", ("mlp_fused_kernel",)), ("qkv_kernel_enabled", ("qkv_fused_kernel",)))),
    ((ATT, BLK, TKG), ()),  # an attention flag whose row serves no call of a program is no fault
], ids=["none", "mlp", "fused-qkv", "qkv-kernel", "mlp+qkv", "attention-flags"])
def test_only_the_projection_flags_are_required_of_a_program(flags, expected):
    """``ModelWrapper._required_strategies`` (the auditor's checker reads it
    too) names the projection kernels alone: they raise where they cannot
    engage. The attention flags are the table's ``flag`` column."""
    from types import SimpleNamespace

    from nxdi_tpu.models.base import causal_lm_forward
    from nxdi_tpu.runtime.model_wrapper import ModelWrapper

    tc = SimpleNamespace(**{f: f in flags for f in (
        "mlp_kernel_enabled", "qkv_kernel_enabled", "fused_qkv", ATT, BLK, TKG)})
    wrapper = SimpleNamespace(forward_fn=causal_lm_forward, config=SimpleNamespace(tpu_config=tc))
    assert ModelWrapper._required_strategies(wrapper) == expected


def test_each_attention_flag_is_one_column_of_the_table():
    from nxdi_tpu.ops import attention_select

    by_flag = {f: tuple(r.name for r in attention_select.TABLE if r.flag == f)
               for f in attention_select.ATTENTION_FLAGS}
    assert by_flag[ATT] == ("mixed_ragged_kernel", "cte_paged_kernel", "cte_flash_kernel")
    assert by_flag[TKG] == ("tkg_fused_kernel_stacked", "tkg_fused_kernel", "tkg_kernel")
    assert by_flag[BLK] == ("tkg_paged_kernel", "tkg_mla_paged_kernel")


def test_the_readme_table_is_the_table():
    """README's "How attention is chosen" rows: names in order, and flags."""
    import os
    import re

    from nxdi_tpu.ops import attention_select

    readme = os.path.join(os.path.dirname(__file__), "..", "..", "README.md")
    with open(readme) as f:
        section = f.read().split("### How attention is chosen")[1].split("\n## ")[0]
    rows = re.findall(r"^\| `(\w+)` \|[^|]*\|[^|]*\| (?:`(\w+)`|—) \|", section, re.M)
    assert rows == [(r.name, r.flag or "") for r in attention_select.TABLE]


def test_a_stack_site_without_a_latent_pool_is_answered_not_crashed():
    """``run_decoder_layers`` builds its site without the value pool: the
    absorbed kernel's row says no instead of indexing ``None``."""
    from nxdi_tpu.ops import attention_select

    site = dataclasses.replace(_site(layout="block", flags=(BLK,)), mla="absorbed")
    assert site.v_pool_shape is None
    assert attention_select.select(site, record=False) == "tkg_mla_paged_xla"


def _gated_attention(flags, pos, layout_inputs, S, seed=0):
    """One attention block with ``attn_out_gate`` over the paged pool, real
    numbers: (output, written pool)."""
    from nxdi_tpu.kvcache.kv_cache import BlockKVCacheSpec, BlockKVLayout
    from nxdi_tpu.models import base

    a = base.DecoderArch(
        num_layers=1, hidden_size=HID, num_attention_heads=H, num_kv_heads=KV, head_dim=D,
        intermediate_size=64, vocab_size=64, vocab_pad=0, dtype="float32", attn_out_gate=True,
        **{f: True for f in flags},
    )
    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def w(*shape):
        return jax.random.normal(next(keys), shape, F32) * 0.3

    p_attn = {"q_proj": {"w": w(HID, H * D)}, "k_proj": {"w": w(HID, KV * D)},
              "v_proj": {"w": w(HID, KV * D)}, "o_proj": {"w": w(H * D, HID)},
              "gate_proj": {"w": w(HID, H * D)}}
    hidden, pool_k, pool_v = w(B, S, HID), w(1, BLOCKS * BLOCK, KV, D), w(1, BLOCKS * BLOCK, KV, D)
    ang = pos[..., None].astype(F32) * (10000.0 ** (-jnp.arange(D // 2) / (D // 2)))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    spec = BlockKVCacheSpec(num_layers=1, num_blocks=BLOCKS, block_size=BLOCK, num_kv_heads=KV,
                            head_dim=D, dtype="float32")
    trace = trace_of()
    trace.clear()
    out, _ = base.attention_block(
        a, p_attn, hidden, cos, sin, pool_k, pool_v, pos, spec, True,
        layout=BlockKVLayout(block_size=BLOCK), cache_inputs=layout_inputs,
        layer_idx=jnp.int32(0),
    )
    return np.asarray(out), tuple(trace)


@pytest.mark.parametrize("S, kernel, flag", [(1, "tkg_paged_kernel", BLK), (8, "cte_paged_kernel", ATT)])
def test_the_output_gate_reaches_the_paged_kernels(S, kernel, flag):
    """Trinity/afmoe's sigmoid gate on the context is applied by the ONE tail
    after every core. The parent's two paged exits called o_proj directly and
    dropped it (no family reached them only because afmoe's per-layer flags
    failed their guards first)."""
    pos = 5 + jnp.broadcast_to(jnp.arange(S, dtype=I32), (B, S))  # 5 tokens already cached
    table = jnp.arange(B * 2, dtype=I32).reshape(B, 2)  # two blocks a row
    slots = jnp.where(pos < BLOCK, table[:, :1] * BLOCK + pos, table[:, 1:2] * BLOCK + pos - BLOCK)
    inputs = {"block_table": table, "slot_mapping": slots}
    gated, took = _gated_attention((flag,), pos, inputs, S)
    reference, fell = _gated_attention((), pos, inputs, S)
    assert took == (kernel,) and fell == ("tkg_xla",)
    np.testing.assert_allclose(gated, reference, rtol=2e-4, atol=2e-5)


if __name__ == "__main__":  # pin the expectations from another tree: see the docstring
    sys.path.insert(0, sys.argv[1])
    jax.config.update("jax_num_cpu_devices", 8)
    for case_name in sorted(CASES):
        got = call(**CASES[case_name][0])
        print(f"{'same  ' if got == CASES[case_name][1] else 'DIFFER'} {case_name}: {got}")
    for case_name in sorted(REFUSED_SINCE_THE_TABLE):
        print(f"       {case_name}: {call(**REFUSED_SINCE_THE_TABLE[case_name][0])}")
    print(f"       bidirectional-spans-over-a-cache: {call(S=8, ci=('bidir_spans',))}")
