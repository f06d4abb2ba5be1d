"""Pallas flash-attention kernels (TPU).

The TPU-native replacements for the reference's NKI attention kernels
(SURVEY §2.9: external ``attention_isa_kernel`` CTE flash,
``attention_tkg_fwd_isa_kernel`` decode, in-repo sliding-window flash
``modules/sliding_window/attention.py:234``). Same role as there: an
*optimization*, never a semantic change. Which call takes which kernel is one
row each of ``ops/attention_select.py TABLE`` (its flag, the mask terms it
computes, its shape and sharding predicates); ops/attention.py is the XLA row
that computes every term.

Design notes (vs the reference's 128-partition NKI tiling):
  - grid = (batch*q_heads, S_q/block_q, S_kv/block_k); the kv dim is the
    innermost (sequential) axis so the online-softmax running state (m, l,
    acc) lives in VMEM scratch across kv steps — the classic flash recipe
    tiled for the 128x128 MXU.
  - positions are AFFINE per row (start + arange) everywhere this framework
    calls attention — prefill arange, decode scalar, speculation windows,
    chunk prefill — so the kernels take per-row scalar STARTS via scalar
    prefetch (SMEM) and rebuild position tiles with 2-D iota in-kernel.
    Mosaic gets no awkward 1-row vector loads, and causal / sliding-window /
    chunked masks still match the XLA path bit-for-bit.
  - causal block skip: a kv block entirely in the future contributes nothing
    and is skipped under ``pl.when`` (the reference's strided-CP kernel
    solves the same wasted-work problem differently).
  - GQA without repeat_kv: q head h reads kv head h // (H/KV) via the
    BlockSpec index map — no materialized head replication in HBM.

On non-TPU backends the kernels run in interpreter mode (tests compare them
against the XLA path on CPU); on TPU they compile with Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nxdi_tpu.ops.kernels import mode

NEG_INF = -30000.0


# single source of truth for the prefill block defaults (cte_probe and the
# A/B harness report these; keep env names in sync)
DEFAULT_PREFILL_BLOCK_Q = 512
DEFAULT_PREFILL_BLOCK_K = 1024


def _pick_block(s: int, target: int) -> int:
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


def prefill_kernel_supported(q_shape, k_shape) -> bool:
    B, H, Sq, D = q_shape
    KV, Sk = k_shape[1], k_shape[2]
    if H % KV:
        return False
    if mode.interpret():
        return True
    # Mosaic pads the lane (head_dim) axis internally — D=64/96 (llama 1B/3B,
    # qwen2, phi) verified bit-compatible on v5e hardware; only the sequence
    # blocks must divide the sublane/lane tiling.
    return D % 8 == 0 and Sq % 8 == 0 and Sk % 128 == 0


def decode_kernel_supported(q_shape, k_shape) -> bool:
    B, H, Sq, D = q_shape
    KV, Sk = k_shape[1], k_shape[2]
    if H % KV or Sq != 1:
        return False
    if mode.interpret():
        return True
    return D % 8 == 0 and Sk % 128 == 0


# ---------------------------------------------------------------------------
# Shared mask math (2-D position tiles from scalar starts)
# ---------------------------------------------------------------------------


def _mask_tile(q_start, kv_start, qi, ki, bq, bk, sliding_window, chunk_size):
    """(bq, bk) bool mask; q row r is position q_start + qi*bq + r, kv col c
    is position kv_start + ki*bk + c."""
    q_pos = q_start + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = kv_start + ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = kv_pos <= q_pos
    if sliding_window is not None:
        m &= kv_pos > q_pos - sliding_window
    if chunk_size is not None:
        m &= (kv_pos // chunk_size) == (q_pos // chunk_size)
    return m


def _online_softmax_step(s, mask, m_ref, l_ref, acc_ref, v, sl=slice(None)):
    """One flash block update of the (m, l, acc) running state; ``sl`` selects
    the scratch rows (the paged kernels keep per-kv-head slices in one
    scratch buffer)."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[sl, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    l_ref[sl, 0] = l_ref[sl, 0] * corr + jnp.sum(p, axis=-1)
    m_ref[sl, 0] = m_new
    # probabilities ride the MXU in the inputs' dtype; accumulate in f32
    acc_ref[sl, :] = acc_ref[sl, :] * corr[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )


# ---------------------------------------------------------------------------
# Prefill (context encoding) kernel
# ---------------------------------------------------------------------------


def _prefill_kernel(
    qs_ref, ks_ref, *refs,
    scale, sliding_window, chunk_size, n_kv_blocks, H, block_q, block_k, has_sink,
    select_block=None, G=1,
):
    """``select_block``: a BLOCK SELECTION rides along (ops/block_select.py): one
    more prefetched array says which (kv head, q tile, kv tile) hold a selected
    block at all (the others are skipped like a tile in the future), and one
    more operand holds, a query row, the bits of its selected blocks: the tile's
    mask keeps a key column iff its block's bit is set."""
    if select_block is not None:
        any_ref, refs = refs[0], refs[1:]
    if has_sink:
        sink_ref, refs = refs[0], refs[1:]
    if select_block is not None:
        q_ref, k_ref, v_ref, bits_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    qi, ki = pl.program_id(1), pl.program_id(2)
    b, head = pl.program_id(0) // H, pl.program_id(0) % H
    q_start = qs_ref[b]
    kv_start = ks_ref[b]

    @pl.when(ki == 0)
    def _():
        if has_sink:
            # a learned sink is one more softmax column a head whose
            # probability is dropped: the running state starts as if that
            # column had been seen (max = the sink's logit, denominator 1)
            m_ref[:] = jnp.full_like(m_ref, sink_ref[head])
            l_ref[:] = jnp.ones_like(l_ref)
        else:
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal skip: kv block entirely in the future of the q block; window
    # skip: entirely behind the window of the q block's first row
    live = kv_start + ki * block_k <= q_start + qi * block_q + block_q - 1
    if sliding_window is not None:
        live &= kv_start + ki * block_k + block_k - 1 > q_start + qi * block_q - sliding_window
    if select_block is not None:
        live &= any_ref[pl.program_id(0) // G, qi, ki] > 0

    @pl.when(live)
    def _():
        q = q_ref[0]  # (block_q, D) — native dtype feeds the MXU
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _mask_tile(
            q_start, kv_start, qi, ki, block_q, block_k, sliding_window, chunk_size
        )
        if select_block is not None:
            # the tile's blocks lie in ONE 32-bit word of a row's selection
            # (block_k // select_block divides 32): pick the word, test the bit
            words = bits_ref[0]  # (block_q, n_words) int32
            first = ki * (block_k // select_block)  # the tile's first block
            lane = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
            word = jnp.sum(jnp.where(lane == first // 32, words, 0), axis=1, keepdims=True)
            bit = first % 32 + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) // select_block
            mask &= ((word >> bit) & 1) == 1
        _online_softmax_step(s, mask, m_ref, l_ref, acc_ref, v)

    @pl.when(ki == n_kv_blocks - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


def flash_attention_prefill(
    q,  # (B, H, Sq, D)
    k,  # (B, KV, Sk, D)
    v,  # (B, KV, Sk, Dv): values of their own width (Dv = D unless the model says so)
    q_pos,  # (B, Sq) int32 — affine per row (start + arange)
    kv_pos,  # (B, Sk) int32 — affine per row
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    chunk_size: Optional[int] = None,
    sink=None,  # (H,) learned sink logits, one a head (gpt-oss, mimo-v2 window layers)
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_mask=None,  # (B, KV, Sq, Sk // select_block) bool: the blocks a query reads
):
    """``sink``: the softmax gains one column a head, ``sink[h]``, whose
    probability is dropped (the running max and denominator start from it).

    ``block_mask``: a block selection (ops/block_select.py): query ``s`` of kv
    head ``g`` attends key ``c`` only where ``block_mask[b, g, s, c //
    select_block]`` (and the position mask holds); a kv tile none of whose
    blocks any query of the q tile selected is skipped. An unselected block adds
    nothing to the softmax: the result is the selection's, not a dense one's.

    512x1024 default blocks: at 128x128 the (B*H, Sq/bq, Sk/bk) grid hits
    ~65k steps/layer at prefill shapes and per-step overhead dominated the
    kernel (xprof: 30 ms/layer vs ~11 ms of FLOPs; 512x512 measured ~3x
    faster end to end on v5e). The round-5 sweep (scripts/kernel_ab.py --cte,
    KERNEL_AB.json) widened K: 512x1024 measured 683 vs 770 ms at the bench
    prefill (bs32 x 1024) — fewer KV-stream restarts per Q block; 256x256
    and 1024x512 both lose. NXDI_TPU_PREFILL_BLOCK_Q/_K override for
    on-chip retuning."""
    import os

    if block_q is None:
        block_q = int(
            os.environ.get("NXDI_TPU_PREFILL_BLOCK_Q", DEFAULT_PREFILL_BLOCK_Q)
        )
    if block_k is None:
        block_k = int(
            os.environ.get("NXDI_TPU_PREFILL_BLOCK_K", DEFAULT_PREFILL_BLOCK_K)
        )
    B, H, Sq, D = q.shape
    KV, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // KV
    scale = D ** -0.5 if scale is None else scale
    block_q = _pick_block(Sq, block_q)
    block_k = _pick_block(Sk, block_k)
    n_kv_blocks = Sk // block_k
    select_block = None
    select_prefetch, select_specs, select_args = [], [], []
    if block_mask is not None:
        n_sel = block_mask.shape[-1]
        select_block = Sk // n_sel
        per_tile = block_k // select_block  # selection blocks a kv tile spans
        if block_k % select_block or 32 % per_tile or block_mask.shape != (B, KV, Sq, n_sel):
            raise ValueError(
                f"block_mask {block_mask.shape} does not tile (B, KV, Sq, Sk // block) with "
                f"kv tiles of {block_k}"
            )
        tiles = block_mask.reshape(B * KV, Sq // block_q, block_q, n_kv_blocks, per_tile)
        select_prefetch = [tiles.any(axis=(2, 4)).astype(jnp.int32)]
        n_words = -(-n_sel // 32)
        padded = jnp.pad(block_mask, ((0, 0),) * 3 + ((0, n_words * 32 - n_sel),))
        weights = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
        words = (padded.reshape(B * KV, Sq, n_words, 32).astype(jnp.uint32) * weights).sum(
            axis=-1, dtype=jnp.uint32)
        select_args = [jax.lax.bitcast_convert_type(words, jnp.int32)]
        select_specs = [pl.BlockSpec(
            (1, block_q, n_words), lambda bh, qi, ki, *_: ((bh // H) * KV + (bh % H) // G, qi, 0))]

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * KV, Sk, D)
    vf = v.reshape(B * KV, Sk, Dv)
    q_start = q_pos[:, 0].astype(jnp.int32)
    kv_start = kv_pos[:, 0].astype(jnp.int32)

    kernel = functools.partial(
        _prefill_kernel,
        scale=scale,
        sliding_window=sliding_window,
        chunk_size=chunk_size,
        n_kv_blocks=n_kv_blocks,
        H=H,
        block_q=block_q,
        block_k=block_k,
        has_sink=sink is not None,
        select_block=select_block,
        G=G,
    )

    def kv_index(bh, qi, ki, *prefetch):
        return (bh // H) * KV + (bh % H) // G, ki, 0

    sink_specs, sink_args = [], []
    if sink is not None:  # H scalars, read one a grid row
        sink_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        sink_args = [sink.astype(jnp.float32).reshape(H)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(select_prefetch),
        grid=(B * H, Sq // block_q, n_kv_blocks),
        in_specs=sink_specs + [
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki, *_: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, Dv), kv_index),
        ] + select_specs,
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda bh, qi, ki, *_: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom
            pltpu.VMEM((block_q, Dv), jnp.float32),  # weighted-V accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, Dv), q.dtype),
        name="flash_attention_prefill",
        interpret=mode.interpret(),
    )(q_start, kv_start, *select_prefetch, *sink_args, qf, kf, vf, *select_args)
    return out.reshape(B, H, Sq, Dv)


# ---------------------------------------------------------------------------
# Decode (token generation) kernel — q_len == 1, KV long
# ---------------------------------------------------------------------------


def _decode_kernel(
    qs_ref, ks_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, sliding_window, chunk_size, n_kv_blocks, KV, block_k,
):
    ki = pl.program_id(1)
    b = pl.program_id(0) // KV
    q_start = qs_ref[b]  # the single decode position (same for all G rows)
    kv_start = ks_ref[b]

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(kv_start + ki * block_k <= q_start)
    def _():
        q = q_ref[0]  # (G, D) — native dtype feeds the MXU
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, block_k)
        G = s.shape[0]
        mask = _mask_tile(
            q_start, kv_start, 0, ki, 1, block_k, sliding_window, chunk_size
        )  # (1, block_k): all G rows decode the same position
        mask = jnp.broadcast_to(mask, (G, block_k))
        _online_softmax_step(s, mask, m_ref, l_ref, acc_ref, v)

    @pl.when(ki == n_kv_blocks - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


def flash_attention_decode(
    q,  # (B, H, 1, D)
    k,  # (B, KV, Sk, D)
    v,  # (B, KV, Sk, D)
    q_pos,  # (B, 1) int32
    kv_pos,  # (B, Sk) int32 — affine per row
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    chunk_size: Optional[int] = None,
    block_k: int = 512,
):
    """Single-position decode: grid over (batch x kv-head) with the G grouped
    query rows as the matmul M dim — one (G, D) x (D, block_k) MXU pass per
    cache block (the reference's TKG kernel role, attention_base.py:1419)."""
    B, H, Sq, D = q.shape
    assert Sq == 1, "decode kernel is single-position"
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    scale = D ** -0.5 if scale is None else scale
    block_k = _pick_block(Sk, block_k)
    n_kv_blocks = Sk // block_k

    qf = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    kf = k.reshape(B * KV, Sk, D)
    vf = v.reshape(B * KV, Sk, D)
    q_start = q_pos[:, 0].astype(jnp.int32)
    kv_start = kv_pos[:, 0].astype(jnp.int32)

    kernel = functools.partial(
        _decode_kernel,
        scale=scale,
        sliding_window=sliding_window,
        chunk_size=chunk_size,
        n_kv_blocks=n_kv_blocks,
        KV=KV,
        block_k=block_k,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * KV, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bk, ki, *_: (bk, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda bk, ki, *_: (bk, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bk, ki, *_: (bk, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda bk, ki, *_: (bk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, D), q.dtype),
        name="flash_attention_decode",
        interpret=mode.interpret(),
    )(q_start, kv_start, qf, kf, vf)
    return out.reshape(B, KV, G, D).reshape(B, H, 1, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused decode kernel — deferred-write composition (cache + fresh row)
# ---------------------------------------------------------------------------


def fused_decode_kernel_supported(q_shape, k_cache_shape) -> bool:
    """Same envelope as the plain decode kernel; the fresh row adds nothing."""
    return decode_kernel_supported(q_shape, k_cache_shape)


def _fused_decode_kernel(
    qs_ref, ks_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, sliding_window, chunk_size, n_kv_blocks, KV, block_k, stacked=False,
):
    ki = pl.program_id(1)
    b = pl.program_id(0) // KV
    q_start = qs_ref[b]  # the single decode position == this step's write slot
    kv_start = ks_ref[b]

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(kv_start + ki * block_k <= q_start)
    def _():
        q = q_ref[0]  # (G, D)
        # S-minor transposed cache view (D, block_k); the stacked variant's
        # blocks carry a leading (1,) layer dim picked by scalar prefetch
        kT = k_ref[0, 0] if stacked else k_ref[0]
        vT = v_ref[0, 0] if stacked else v_ref[0]
        # VPU broadcast-multiply-reduce: with M = G (typically 4-8) an MXU
        # matmul wastes ~97% of the systolic array; the elementwise form
        # matches XLA's own near-roofline decode lowering
        s = jnp.sum(
            q.astype(jnp.float32)[:, :, None] * kT.astype(jnp.float32)[None, :, :],
            axis=1,
        ) * scale  # (G, block_k)
        G = s.shape[0]
        # STRICT causal mask over the cache: the slot AT q_start holds last
        # step's (stale) row — the fresh row below replaces it (deferred-write
        # semantics, attention_two_part's poisoned-slot mask with T == 1)
        kv_pos = kv_start + ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        mask = kv_pos < q_start
        if sliding_window is not None:
            mask &= kv_pos > q_start - sliding_window
        if chunk_size is not None:
            mask &= (kv_pos // chunk_size) == (q_start // chunk_size)
        mask = jnp.broadcast_to(mask, (G, block_k))
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
        m_ref[:, 0] = m_new
        acc_ref[:] = acc_ref[:] * corr[:, None] + jnp.sum(
            p[:, None, :] * vT.astype(jnp.float32)[None, :, :], axis=2
        )

    @pl.when(ki == n_kv_blocks - 1)
    def _():
        # fold in the fresh row (position q_start; always attended — its own
        # position satisfies every causal/window/chunk mask). The (G, 1) dot
        # is a VPU reduction — Mosaic rejects an MXU matmul with N == 1.
        q = q_ref[0]
        kn = kn_ref[0]  # (1, D)
        vn = vn_ref[0]
        s2 = jnp.sum(
            q.astype(jnp.float32) * kn.astype(jnp.float32), axis=-1
        ) * scale  # (G,)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s2)
        corr = jnp.exp(m_prev - m_new)
        p2 = jnp.exp(s2 - m_new)
        l = l_ref[:, 0] * corr + p2
        acc = acc_ref[:] * corr[:, None] + p2[:, None] * vn.astype(jnp.float32)
        l = jnp.maximum(l, 1e-20)
        o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


def _fused_decode_stacked_kernel(li_ref, qs_ref, ks_ref, *rest, **kw):
    del li_ref  # consumed by the cache index maps
    _fused_decode_kernel(qs_ref, ks_ref, *rest, stacked=True, **kw)


def flash_attention_decode_fused_stacked(
    q,  # (B, H, 1, D)
    k_cache_s,  # (L, B, KV, Sk, D) — FULL stacked OLD cache
    v_cache_s,
    k_new,  # (B, KV, 1, D) — this step's fresh row
    v_new,
    q_pos,  # (B, 1)
    layer_idx,  # scalar/1-elt int32 — the in-scan layer index
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    chunk_size: Optional[int] = None,
    block_k: int = 512,
    kv_len: Optional[int] = None,
):
    """The STACKED form of :func:`flash_attention_decode_fused`: the cache
    operand is the whole (L, B, KV, S, D) stack and the active layer is
    selected by a scalar-prefetched index — inside the decoder ``lax.scan`` a
    pallas operand on the per-layer cache slice materializes a full-cache
    copy per layer (the round-3 finding that made the per-layer kernel LOSE
    to XLA two-part, bench.py notes); indexing the stack in the BlockSpec
    reads only the touched blocks, like ops/kernels/kv_commit.py.

    Same contract as the per-layer kernel otherwise (strict-causal old-cache
    mask + fresh-row fold; contiguous layout kv positions = 0..Sk-1)."""
    B, H, Sq, D = q.shape
    assert Sq == 1, "fused decode kernel is single-position"
    L, KV, Sk = k_cache_s.shape[0], k_cache_s.shape[2], k_cache_s.shape[3]
    G = H // KV
    scale = D ** -0.5 if scale is None else scale
    attended = Sk if kv_len is None else min(kv_len, Sk)
    block_k = _pick_block(attended, block_k)
    n_kv_blocks = attended // block_k

    qf = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    # S-minor bitcast view of the stacked cache (L, B*KV, D, Sk)
    kf = jnp.swapaxes(k_cache_s, 3, 4).reshape(L, B * KV, D, Sk)
    vf = jnp.swapaxes(v_cache_s, 3, 4).reshape(L, B * KV, D, Sk)
    knf = k_new.reshape(B * KV, 1, D)
    vnf = v_new.reshape(B * KV, 1, D)
    q_start = q_pos[:, 0].astype(jnp.int32)
    kv_start = jnp.zeros((B,), jnp.int32)  # contiguous layout positions
    li = jnp.asarray(layer_idx, jnp.int32).reshape(1)

    kernel = functools.partial(
        _fused_decode_stacked_kernel,
        scale=scale,
        sliding_window=sliding_window,
        chunk_size=chunk_size,
        n_kv_blocks=n_kv_blocks,
        KV=KV,
        block_k=block_k,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * KV, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bk, ki, *_: (bk, 0, 0)),
            pl.BlockSpec(
                (1, 1, D, block_k), lambda bk, ki, li_ref, *_: (li_ref[0], bk, 0, ki)
            ),
            pl.BlockSpec(
                (1, 1, D, block_k), lambda bk, ki, li_ref, *_: (li_ref[0], bk, 0, ki)
            ),
            pl.BlockSpec((1, 1, D), lambda bk, ki, *_: (bk, 0, 0)),
            pl.BlockSpec((1, 1, D), lambda bk, ki, *_: (bk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda bk, ki, *_: (bk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, D), q.dtype),
        name="flash_attention_decode_fused_stacked",
        interpret=mode.interpret(),
    )(li, q_start, kv_start, qf, kf, vf, knf, vnf)
    return out.reshape(B, KV, G, D).reshape(B, H, 1, D).astype(q.dtype)


def sharded_fused_decode_stacked_call(
    policy, q, k_cache_s, v_cache_s, k_new, v_new, q_pos, layer_idx,
    *, scale=None, sliding_window=None, chunk_size=None, kv_len=None,
):
    """Stacked fused decode under GSPMD (the KV sequence dim unsharded: the
    attention table's sharding predicate)."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        flash_attention_decode_fused_stacked,
        scale=scale,
        sliding_window=sliding_window,
        chunk_size=chunk_size,
        kv_len=kv_len,
    )
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return fn(q, k_cache_s, v_cache_s, k_new, v_new, q_pos, layer_idx)
    kv_spec = policy.cache_kv
    q_spec = P(*policy.q)
    fresh_spec = P(*policy.kv)
    cache_spec = P(None, *kv_spec)
    qp_spec = P(policy.q[0], policy.q[2])
    shard_fn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, fresh_spec, fresh_spec,
                  qp_spec, P()),
        out_specs=q_spec,
        check_vma=False,
    )
    return shard_fn(q, k_cache_s, v_cache_s, k_new, v_new, q_pos, layer_idx)


def flash_attention_decode_fused(
    q,  # (B, H, 1, D)
    k_cache,  # (B, KV, Sk, D) — OLD cache (this step's slot stale)
    v_cache,  # (B, KV, Sk, D)
    k_new,  # (B, KV, 1, D) — this step's fresh row
    v_new,  # (B, KV, 1, D)
    q_pos,  # (B, 1) int32 decode position == write slot
    kv_pos,  # (B, Sk) int32 — affine per row
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    chunk_size: Optional[int] = None,
    block_k: int = 512,
    kv_len: Optional[int] = None,
):
    """Deferred-write decode attention in ONE kernel: online-softmax over the
    old cache with a STRICT causal mask (this step's slot excluded) merged
    with the fresh K/V row — the kernel form of ops/attention.py
    ``attention_two_part`` for T == 1 (reference: the fused TKG kernels,
    attention_base.py:1419-1994). Composes with the Pallas commit kernel
    (kv_commit.py): the step never materializes an updated cache view.

    ``kv_len`` statically bounds how many cache positions are attended (the
    bucket's KV window) WITHOUT slicing the cache — the grid just stops
    early, so no windowed copy of the cache is materialized for the kernel.

    The cache operands ride the S-minor TRANSPOSED view (B*KV, D, Sk): the
    decode program's preferred cache layout is sequence-minor, so the
    swapaxes below is a layout-preserving bitcast — feeding the cache to the
    kernel untransposed costs a full relayout copy per layer (measured: the
    kernel was 3x SLOWER than the XLA path until the view matched).
    """
    B, H, Sq, D = q.shape
    assert Sq == 1, "fused decode kernel is single-position"
    KV, Sk = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = D ** -0.5 if scale is None else scale
    attended = Sk if kv_len is None else min(kv_len, Sk)
    block_k = _pick_block(attended, block_k)
    n_kv_blocks = attended // block_k

    qf = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    kf = jnp.swapaxes(k_cache, 2, 3).reshape(B * KV, D, Sk)  # bitcast view
    vf = jnp.swapaxes(v_cache, 2, 3).reshape(B * KV, D, Sk)
    knf = k_new.reshape(B * KV, 1, D)
    vnf = v_new.reshape(B * KV, 1, D)
    q_start = q_pos[:, 0].astype(jnp.int32)
    kv_start = kv_pos[:, 0].astype(jnp.int32)

    kernel = functools.partial(
        _fused_decode_kernel,
        scale=scale,
        sliding_window=sliding_window,
        chunk_size=chunk_size,
        n_kv_blocks=n_kv_blocks,
        KV=KV,
        block_k=block_k,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * KV, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bk, ki, *_: (bk, 0, 0)),
            pl.BlockSpec((1, D, block_k), lambda bk, ki, *_: (bk, 0, ki)),
            pl.BlockSpec((1, D, block_k), lambda bk, ki, *_: (bk, 0, ki)),
            pl.BlockSpec((1, 1, D), lambda bk, ki, *_: (bk, 0, 0)),
            pl.BlockSpec((1, 1, D), lambda bk, ki, *_: (bk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda bk, ki, *_: (bk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, D), q.dtype),
        name="flash_attention_decode_fused",
        interpret=mode.interpret(),
    )(q_start, kv_start, qf, kf, vf, knf, vnf)
    return out.reshape(B, KV, G, D).reshape(B, H, 1, D).astype(q.dtype)


def sharded_fused_decode_call(
    policy, q, k_cache, v_cache, k_new, v_new, q_pos, kv_pos,
    *, scale=None, sliding_window=None, chunk_size=None, kv_len=None,
):
    """Fused deferred-write decode under GSPMD (see sharded_kernel_call)."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        flash_attention_decode_fused,
        scale=scale,
        sliding_window=sliding_window,
        chunk_size=chunk_size,
        kv_len=kv_len,
    )
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return fn(q, k_cache, v_cache, k_new, v_new, q_pos, kv_pos)
    kv_spec = policy.cache_kv
    q_spec = P(*policy.q)
    fresh_spec = P(*policy.kv)
    qp_spec = P(policy.q[0], policy.q[2])
    kp_spec = P(kv_spec[0], None)
    shard_fn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(q_spec, P(*kv_spec), P(*kv_spec), fresh_spec, fresh_spec,
                  qp_spec, kp_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return shard_fn(q, k_cache, v_cache, k_new, v_new, q_pos, kv_pos)


# ---------------------------------------------------------------------------
# Paged (block-table) decode kernel
# ---------------------------------------------------------------------------


def paged_decode_kernel_supported(q_shape, cache_shape, block_size, v_cache_shape=None) -> bool:
    """``cache_shape`` is the stacked key pool's (L, total_slots, KV, D);
    ``v_cache_shape`` the value pool's where its width is its own."""
    B, H, Sq, D = q_shape
    total_slots, KV = cache_shape[1], cache_shape[2]
    if H % KV or Sq != 1 or total_slots % block_size:
        return False
    Dv, tiles = D, 1
    if v_cache_shape is not None:
        if tuple(v_cache_shape[1:3]) != tuple(cache_shape[1:3]) or cache_shape[0] % v_cache_shape[0]:
            return False
        Dv, tiles = v_cache_shape[3], cache_shape[0] // v_cache_shape[0]
    if D != tiles * cache_shape[3] or (tiles > 1 and cache_shape[3] % 128):
        return False  # a key row is one pool row, or whole lane tiles of one
    if mode.interpret():
        return True
    # the cache block is (block_size, KV, D): Mosaic needs the last two dims
    # (KV, D) full (they are) and the head count small enough that the
    # per-head python loop stays reasonable
    return D % 8 == 0 and Dv % 8 == 0 and block_size % 8 == 0 and KV <= 16


#: table entries whose blocks one grid step of the paged decode kernel
#: fetches at once: (2 buffers) x K,V x 8 x (128, KV, 128) bf16 blocks of a
#: GQA model is a few MiB of VMEM, and a row of <= 1024 tokens is one step
PAGED_DECODE_PAGES_PER_STEP = 8


def _reset_softmax_state(m_ref, l_ref, acc_ref):
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _write_paged_decode_output(o_ref, l_ref, acc_ref, v_scale, KV, G):
    l = jnp.maximum(l_ref[:, 0], 1e-20)
    o_ref[0] = (
        (acc_ref[:] * v_scale / l[:, None])
        .reshape(KV, G, acc_ref.shape[-1])
        .astype(o_ref.dtype)
    )


def _paged_block_chain(
    b, c, bt_ref, qp_ref, layer, pools, bufs, sem, slot_ref,
    *, n_rows, n_chunks, pages, block_size, rows, reset, prepare, block,
):
    """The chain of block copies of a paged decode kernel with grid (row,
    chunk of ``pages`` table entries), shared by ``_paged_decode_kernel`` and
    ``mla_decode._mla_paged_decode_kernel``. The pools stay in HBM; each step
    that has live blocks waits for its own (started one live step earlier,
    into the other buffer), starts the NEXT live step's — the same row's next
    chunk, or the next row's first — and computes, so a block's HBM latency
    is paid behind the previous step's compute and all blocks of a step are
    in flight together. Steps past a row's position touch neither the pools
    nor the state.

    ``pools``: HBM refs (L, pool rows, width), one block being ``rows`` of
    them, each read at ``layer`` (one index, or one a pool); ``bufs``: their VMEM buffers (2, pages, rows, width); ``sem``: DMA
    semaphores (2, len(pools), pages); ``slot_ref``: SMEM (1,), the buffer
    the current step reads. ``reset()`` runs at a row's first chunk, ``ctx =
    prepare()`` once in a step that computes, ``block(ctx, p, slot, q_pos)``
    on table entry ``p`` of the chunk once its copies have landed. ``b, c``:
    the grid step (``pl.program_id`` of both axes)."""
    span = pages * block_size  # positions one chunk covers
    layers = layer if isinstance(layer, tuple) else (layer,) * len(pools)

    def page_copies(row, chunk, slot):
        """[(live, one copy a pool)] of one step's table entries: a block is
        fetched iff it is allocated and starts at or before the position."""
        out = []
        for p in range(pages):
            entry = chunk * pages + p
            blk = bt_ref[row, entry]
            live = (blk >= 0) & (entry * block_size <= qp_ref[row])
            src = pl.ds(pl.multiple_of(jnp.maximum(blk, 0) * rows, rows), rows)
            out.append((
                live,
                [
                    pltpu.make_async_copy(
                        hbm.at[at, src], buf.at[slot, p], sem.at[slot, i, p]
                    )
                    for i, (hbm, buf, at) in enumerate(zip(pools, bufs, layers))
                ],
            ))
        return out

    def start(row, chunk, slot):
        for live, copies in page_copies(row, chunk, slot):
            @pl.when(live)
            def _():
                for copy in copies:
                    copy.start()

    @pl.when((b == 0) & (c == 0))
    def _():
        slot_ref[0] = 0
        start(0, 0, 0)

    @pl.when(c == 0)
    def _():
        reset()

    # a row's first chunk always runs (it hands the chain of prefetches on,
    # even for a row with nothing to read); later ones while they hold
    # positions the row has reached
    @pl.when((c == 0) | (c * span <= qp_ref[b]))
    def _():
        slot = slot_ref[0]
        q_pos = qp_ref[b]
        same_row = (c + 1 < n_chunks) & ((c + 1) * span <= q_pos)
        nxt_row = jnp.where(same_row, b, b + 1)
        nxt_chunk = jnp.where(same_row, c + 1, 0)

        @pl.when(nxt_row < n_rows)
        def _():
            start(nxt_row, nxt_chunk, 1 - slot)

        ctx = prepare()
        for p, (live, copies) in enumerate(page_copies(b, c, slot)):
            @pl.when(live)
            def _(p=p, copies=copies):
                for copy in copies:
                    copy.wait()
                block(ctx, p, slot, q_pos)

        slot_ref[0] = 1 - slot


def _paged_decode_kernel(
    li_ref, bt_ref, qp_ref, q_ref, k_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref, *rest,
    scale, v_scale, n_rows, n_chunks, pages, KV, G, block_size, compute_dtype, key_tiles,
):
    """Grid (row, chunk of ``pages`` table entries); ``_paged_block_chain``
    brings the blocks.

    A block arrives as its (block_size * KV, D) rows, token-major with the kv
    heads interleaved (row = token * KV + head): ONE dot of all H query rows
    against it scores every head at once, and the mask keeps, for a query
    row, the columns of its own kv head (the others' exp is an exact 0, so
    they add nothing to l or acc). That spends KV x the MXU work a per-head
    dot needs — nothing beside the block's DMA at decode widths — and never
    pulls a head's rows out from between the others'.

    ``key_tiles`` > 1: a key row wider than the pool's rows is kept as that
    many lane tiles, tile ``j`` of layer ``l`` in pool layer ``j * L + l``
    (``kvcache BlockKVLayout``): each tile is a block copy of its own from the
    same key pool, and the score is the sum of the tiles' dots."""
    k_bufs, (v_buf, sem, slot_ref) = rest[:key_tiles], rest[key_tiles:]
    b, c = pl.program_id(0), pl.program_id(1)
    layer = li_ref[0]
    rows = block_size * KV  # pool rows of one block
    n_layers = k_hbm.shape[0] // key_tiles
    width = k_hbm.shape[-1]

    def prepare():
        q = q_ref[0].reshape(KV * G, q_ref.shape[-1])  # row = head * G + g
        col = jax.lax.broadcasted_iota(jnp.int32, (KV * G, rows), 1)
        q_head = jax.lax.broadcasted_iota(jnp.int32, (KV * G, rows), 0) // G
        return q, (col % KV) == q_head, col // KV

    def block(ctx, p, slot, q_pos):
        q, own_head, token = ctx
        v = v_buf[slot, p].astype(compute_dtype)
        s = None
        for j, k_buf in enumerate(k_bufs):
            k = k_buf[slot, p].astype(compute_dtype)  # (block_size * KV, width)
            part = jax.lax.dot_general(
                q if key_tiles == 1 else q[:, j * width:(j + 1) * width], k,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )  # (H, block_size * KV)
            s = part if s is None else s + part
        kv_pos = (c * pages + p) * block_size + token
        mask = own_head & (kv_pos <= q_pos)
        _online_softmax_step(s * scale, mask, m_ref, l_ref, acc_ref, v)

    _paged_block_chain(
        b, c, bt_ref, qp_ref,
        tuple(layer + j * n_layers for j in range(key_tiles)) + (layer,),
        (k_hbm,) * key_tiles + (v_hbm,), tuple(k_bufs) + (v_buf,), sem, slot_ref,
        n_rows=n_rows, n_chunks=n_chunks, pages=pages, block_size=block_size, rows=rows,
        reset=lambda: _reset_softmax_state(m_ref, l_ref, acc_ref),
        prepare=prepare, block=block,
    )

    @pl.when(c == n_chunks - 1)
    def _():
        _write_paged_decode_output(o_ref, l_ref, acc_ref, v_scale, KV, G)


def _paged_decode_narrow_kernel(
    li_ref, bt_ref, qp_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, v_scale, n_blocks, KV, G, block_size, compute_dtype,
):
    """Heads narrower than a lane tile (D = 64, 96): one (block_size, KV, D)
    BlockSpec block per (row, table entry) grid step, pipelined by Pallas —
    Mosaic cannot slice such a pool in HBM for a copy of the kernel's own."""
    del li_ref  # consumed by the cache index maps
    bi = pl.program_id(1)
    b = pl.program_id(0)
    q_pos = qp_ref[b]
    bt = bt_ref[b, bi]

    @pl.when(bi == 0)
    def _():
        _reset_softmax_state(m_ref, l_ref, acc_ref)

    # skip unallocated blocks and blocks entirely past the decode position
    @pl.when((bt >= 0) & (bi * block_size <= q_pos))
    def _():
        kv_pos = bi * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1
        )
        mask = jnp.broadcast_to(kv_pos <= q_pos, (G, block_size))
        # one cache-block read serves every kv head (the block's last two
        # dims are the FULL (KV, D) tail — Mosaic-valid for any KV)
        for kv in range(KV):
            q = q_ref[0, kv]  # (G, D)
            k = k_ref[:, kv, :].astype(compute_dtype)  # (block_size, D)
            v = v_ref[:, kv, :].astype(compute_dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (G, block_size)
            _online_softmax_step(
                s, mask, m_ref, l_ref, acc_ref, v, sl=slice(kv * G, (kv + 1) * G)
            )

    @pl.when(bi == n_blocks - 1)
    def _():
        _write_paged_decode_output(o_ref, l_ref, acc_ref, v_scale, KV, G)


def paged_attention_decode(
    q,  # (B, H, 1, D)
    k_cache,  # (L, total_slots, KV, D) — the WHOLE layer-stacked paged pool
    v_cache,  # (L, total_slots, KV, Dv): values at their own width
    block_table,  # (B, NB) int32 block ids in logical token order; <0 = hole
    q_pos,  # (B, 1) int32 decode positions
    layer_idx,  # scalar/1-elt int32 — the layer of the stack to read
    *,
    block_size: int,
    scale: Optional[float] = None,
    k_scale: float = 1.0,
    v_scale: float = 1.0,
):
    """Decode attention reading K/V **through the block table** — no
    materialized (B, KV, W, D) gather in HBM (the round-1 XLA path's
    O(table-width) traffic; reference analog: NKI block-TKG kernel,
    attention_base.py:50-162). Prefix-cached blocks are just table entries —
    nothing special. fp8 scaled caches fold ``k_scale`` into the softmax
    scale and ``v_scale`` into the output normalization (exact, since both
    are per-tensor).

    The pool operand is the whole (L, slots, KV, D) stack and the layer is one
    more prefetched scalar: inside the decoder's layer scan a Pallas operand
    on a per-layer slice of the pool materializes the slice (and made the
    scan copy the pool). A block is ONE copy for all kv heads.

    Keys and values each have their own width (the score dot runs over D,
    the accumulator and the output are Dv wide). A width that is no multiple
    of the 128-lane tile cannot be copied from HBM by the kernel itself:
    Mosaic refuses the slice (a 192-wide row sits in 256 lanes of HBM either
    way), and a row of SEVERAL lane tiles beside a handful of kv heads is laid
    out by XLA (tiles of (KV, 128)) so that the (slots * KV, D) view below is
    a copy of the pool, not a bitcast. So a model with such keys keeps them
    zero-padded to whole lane tiles, ONE tile a pool row: ``k_cache`` is
    (tiles * L, slots, KV, 128), tile ``j`` of layer ``l`` at pool layer
    ``j * L + l`` (models/mimo_v2: 192 -> 2 tiles; the queries padded alike
    at the call, so the scores are the 192-wide ones), the tile count read
    from the two pools' layer counts, and each tile is a block copy of its
    own, whole tiles like every other.

    At lane-wide heads (D and Dv multiples of 128) the pool stays in HBM and the
    kernel fetches the blocks itself (``_paged_decode_kernel``): the live
    blocks of up to ``PAGED_DECODE_PAGES_PER_STEP`` table entries fly
    together, one step ahead of the compute, through the pool's
    (L, slots * KV, D) view — the same bytes (XLA makes the reshape a
    bitcast), moved as 4 KiB tiles where the (.., KV, D) view moves KV-row
    tiles (512 B at KV = 2), and from HBM the tile count is what a copy's
    time goes by: 36 launches over 333 live blocks took 59 ms with one
    (block_size, KV, D) BlockSpec block a step, 52 ms with eight of those in
    flight, 7.0 ms with eight on this view (PERF.md, PR 27). Narrower heads
    keep the BlockSpec form (``_paged_decode_narrow_kernel``)."""
    B, H, Sq, D = q.shape
    assert Sq == 1, "paged decode kernel is single-position"
    L, slots, KV, W = k_cache.shape
    Dv = v_cache.shape[3]
    key_tiles = L // v_cache.shape[0]  # lane tiles a key row is kept as (D = key_tiles * W)
    G = H // KV
    NB = block_table.shape[1]
    scale = (D ** -0.5 if scale is None else scale) * k_scale
    static = dict(
        scale=scale, v_scale=v_scale, KV=KV, G=G, block_size=block_size,
        compute_dtype=q.dtype,
    )
    q_spec = pl.BlockSpec((1, KV, G, D), lambda b, i, *_: (b, 0, 0, 0))
    o_spec = pl.BlockSpec((1, KV, G, Dv), lambda b, i, *_: (b, 0, 0, 0))
    state = [  # the running (m, l, acc) of one row, all heads
        pltpu.VMEM((KV * G, 1), jnp.float32),
        pltpu.VMEM((KV * G, 1), jnp.float32),
        pltpu.VMEM((KV * G, Dv), jnp.float32),
    ]
    bt = block_table.astype(jnp.int32)
    if W % 128 == 0 and Dv % 128 == 0:
        pages = min(PAGED_DECODE_PAGES_PER_STEP, NB)
        n_chunks = -(-NB // pages)
        if n_chunks * pages != NB:  # entries past the table are holes
            bt = jnp.pad(bt, ((0, 0), (0, n_chunks * pages - NB)), constant_values=-1)
        k_cache = k_cache.reshape(L, slots * KV, W)
        v_cache = v_cache.reshape(v_cache.shape[0], slots * KV, Dv)
        kernel = functools.partial(
            _paged_decode_kernel, n_rows=B, n_chunks=n_chunks, pages=pages,
            key_tiles=key_tiles, **static
        )
        grid = (B, n_chunks)
        pool_spec = v_pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        buf = (2, pages, block_size * KV)  # [buffer, table entry] blocks
        scratch = state + [pltpu.VMEM(buf + (W,), k_cache.dtype)] * key_tiles + [
            pltpu.VMEM(buf + (Dv,), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, key_tiles + 1, pages)),  # [buffer, K tiles | V, entry]
            pltpu.SMEM((1,), jnp.int32),  # the buffer the current step reads
        ]
    else:
        assert key_tiles == 1, "narrow heads are one pool row"
        kernel = functools.partial(_paged_decode_narrow_kernel, n_blocks=NB, **static)
        grid = (B, NB)

        def cache_index(b, bi, li_ref, bt_ref, qp_ref):
            # unallocated/future blocks clamp to block 0 — the kernel masks them out
            return li_ref[0], jnp.maximum(bt_ref[b, bi], 0), 0, 0

        pool_spec = pl.BlockSpec((None, block_size, KV, W), cache_index)
        v_pool_spec = pl.BlockSpec((None, block_size, KV, Dv), cache_index)
        scratch = state
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[q_spec, pool_spec, v_pool_spec],
            out_specs=o_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dv), q.dtype),
        name="paged_attention_decode",
        interpret=mode.interpret(),
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1), bt, q_pos[:, 0].astype(jnp.int32),
        q.reshape(B, KV, G, D), k_cache, v_cache,
    )
    return out.reshape(B, H, 1, Dv)


def paged_prefill_kernel_supported(q_shape, cache_shape, block_size) -> bool:
    """``cache_shape`` is the stacked pool's (L, total_slots, KV, D)."""
    B, H, Sq, D = q_shape
    total_slots, KV = cache_shape[1], cache_shape[2]
    G = H // KV if H % KV == 0 else 0
    if not G or total_slots % block_size:
        return False
    if mode.interpret():
        return True
    return D % 8 == 0 and block_size % 128 == 0 and Sq % 8 == 0 and KV <= 16


def _paged_prefill_kernel(
    li_ref, bt_ref, qs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, v_scale, n_blocks, KV, G, block_q, block_size, compute_dtype,
):
    del li_ref  # consumed by the cache index maps
    qi, bi = pl.program_id(1), pl.program_id(2)
    b = pl.program_id(0)
    q_start = qs_ref[b]
    bt = bt_ref[b, bi]

    @pl.when(bi == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # skip unallocated blocks and blocks entirely past this q tile
    @pl.when((bt >= 0) & (bi * block_size <= q_start + qi * block_q + block_q - 1))
    def _():
        # row r is query position q_start + qi*block_q + r; kv col c is
        # LOGICAL position bi*block_size + c (table order); one cache block
        # read serves every kv head (full (KV, D) block tail)
        q_pos = (
            q_start
            + qi * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_size), 0)
        )
        kv_pos = bi * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 1
        )
        base_mask = kv_pos <= q_pos
        for kv in range(KV):
            q = q_ref[0, kv].reshape(G * block_q, q_ref.shape[-1])
            k = k_ref[:, kv, :].astype(compute_dtype)  # (block_size, D)
            v = v_ref[:, kv, :].astype(compute_dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (G*bq, block_size)
            mask = jnp.broadcast_to(
                base_mask[None], (G, block_q, block_size)
            ).reshape(G * block_q, block_size)
            _online_softmax_step(
                s, mask, m_ref, l_ref, acc_ref, v,
                sl=slice(kv * G * block_q, (kv + 1) * G * block_q),
            )

    @pl.when(bi == n_blocks - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[0] = (
            (acc_ref[:] * v_scale / l[:, None])
            .reshape(KV, G, block_q, acc_ref.shape[-1])
            .astype(o_ref.dtype)
        )


def paged_attention_prefill(
    q,  # (B, H, Sq, D) — the active chunk/suffix queries
    k_cache,  # (L, total_slots, KV, D) — stacked paged pool, chunk already written
    v_cache,  # (L, total_slots, KV, D)
    block_table,  # (B, NB) int32 block ids in logical token order; <0 = hole
    q_pos,  # (B, Sq) int32 — affine per row (chunk start + arange)
    layer_idx,  # scalar/1-elt int32 — the layer of the stack to read
    *,
    block_size: int,
    scale: Optional[float] = None,
    k_scale: float = 1.0,
    v_scale: float = 1.0,
    block_q: int = 256,
):
    """Prefix-cache / chunked-prefill CTE attention reading K/V **through the
    block table** — the multi-token-q extension of ``paged_attention_decode``
    (reference: the NKI block-CTE kernels, attention_base.py:50-162,909,1083).
    HBM traffic is one pass over the LIVE blocks per kv head instead of the
    XLA path's materialized (B, KV, NB*block_size, D) gather; prefix-cached
    blocks are just table entries. The chunk's own K/V must already be
    scattered into the pool (BlockKVLayout.update runs first), so new tokens
    attend earlier tokens of the same chunk through the table like the
    reference's contexted prefill. The pool is the whole layer stack, read at
    ``layer_idx`` (see ``paged_attention_decode``)."""
    B, H, Sq, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    NB = block_table.shape[1]
    scale = (D ** -0.5 if scale is None else scale) * k_scale
    compute_dtype = q.dtype
    # bound the softmax state (KV*G*bq rows of f32 scratch) against VMEM
    block_q = _pick_block(Sq, max(8, min(block_q, 4096 // max(H, 1))))

    qf = q.reshape(B, KV, G, Sq, D)
    bt = block_table.astype(jnp.int32)
    qs = q_pos[:, 0].astype(jnp.int32)
    li = jnp.asarray(layer_idx, jnp.int32).reshape(1)

    kernel = functools.partial(
        _paged_prefill_kernel,
        scale=scale,
        v_scale=v_scale,
        n_blocks=NB,
        KV=KV,
        G=G,
        block_q=block_q,
        block_size=block_size,
        compute_dtype=compute_dtype,
    )

    def cache_index(b, qi, bi, li_ref, bt_ref, qs_ref):
        return li_ref[0], jnp.maximum(bt_ref[b, bi], 0), 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Sq // block_q, NB),
        in_specs=[
            pl.BlockSpec(
                (1, KV, G, block_q, D), lambda b, qi, bi, *_: (b, 0, 0, qi, 0)
            ),
            pl.BlockSpec((None, block_size, KV, D), cache_index),
            pl.BlockSpec((None, block_size, KV, D), cache_index),
        ],
        out_specs=pl.BlockSpec(
            (1, KV, G, block_q, D), lambda b, qi, bi, *_: (b, 0, 0, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((KV * G * block_q, 1), jnp.float32),
            pltpu.VMEM((KV * G * block_q, 1), jnp.float32),
            pltpu.VMEM((KV * G * block_q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Sq, D), q.dtype),
        name="paged_attention_prefill",
        interpret=mode.interpret(),
    )(li, bt, qs, qf, k_cache, v_cache)
    return out.reshape(B, H, Sq, D)


def sharded_paged_prefill_call(
    policy, q, k_cache, v_cache, block_table, q_pos, layer_idx,
    *, block_size, scale=None, k_scale=1.0, v_scale=1.0,
):
    """Paged prefill under GSPMD (see sharded_paged_decode_call): the stacked
    pool and q shard over kv heads on tp; table, positions and the layer
    index are replicated."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        paged_attention_prefill,
        block_size=block_size,
        scale=scale,
        k_scale=k_scale,
        v_scale=v_scale,
    )
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return fn(q, k_cache, v_cache, block_table, q_pos, layer_idx)
    shard_fn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(*policy.q),
            P(None, None, policy.q[1], None),
            P(None, None, policy.q[1], None),
            P(None, None),
            P(None, None),
            P(),
        ),
        out_specs=P(*policy.q),
        check_vma=False,
    )
    return shard_fn(q, k_cache, v_cache, block_table, q_pos, layer_idx)


def sharded_paged_decode_call(
    policy, q, k_cache, v_cache, block_table, q_pos, layer_idx,
    *, block_size, scale=None, k_scale=1.0, v_scale=1.0,
):
    """Paged decode under GSPMD: the stacked pool + q shard over kv-heads on
    tp, the block table, positions and the layer index are replicated."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        paged_attention_decode,
        block_size=block_size,
        scale=scale,
        k_scale=k_scale,
        v_scale=v_scale,
    )
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return fn(q, k_cache, v_cache, block_table, q_pos, layer_idx)
    # the block pool is (L, slots, KV, D), sharded on heads only
    shard_fn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(*policy.q),
            P(None, None, policy.q[1], None),
            P(None, None, policy.q[1], None),
            P(None, None),
            P(None, None),
            P(),
        ),
        out_specs=P(*policy.q),
        check_vma=False,
    )
    return shard_fn(q, k_cache, v_cache, block_table, q_pos, layer_idx)


# ---------------------------------------------------------------------------
# Sharded dispatch — kernels under GSPMD
# ---------------------------------------------------------------------------


def sharded_kernel_call(
    policy,
    q, k, v, q_pos, kv_pos,
    *,
    decode: bool,
    scale=None,
    sliding_window=None,
    chunk_size=None,
    sink=None,
    block_mask=None,
):
    """Run the flash kernel per mesh shard via ``shard_map`` (GSPMD cannot
    partition a pallas_call by itself). Head/batch shardings follow the
    submodel's :class:`ShardingPolicy`; attention is head-local so no in-shard
    collectives are needed. CP's q-sequence sharding is fine — GSPMD shards
    are contiguous slices, so per-shard positions stay affine and each shard's
    start is its own ``row[0]``. A policy that shards the KV sequence dim
    (flash decoding needs a cross-shard softmax) is the XLA rows': the
    attention table never selects this call for it."""
    from jax.sharding import PartitionSpec as P

    base = functools.partial(
        flash_attention_decode if decode else flash_attention_prefill,
        scale=scale,
        sliding_window=sliding_window,
        chunk_size=chunk_size,
    )
    kv_spec = policy.cache_kv if decode else policy.kv
    # the prefill kernel's alone (the table's ``computes``): one logit a head,
    # sharded as the heads; a block selection a kv head and query token
    extras = [
        (name, operand, spec)
        for name, operand, spec in (
            ("sink", sink, P(policy.q[1])),
            ("block_mask", block_mask, P(policy.q[0], kv_spec[1], policy.q[2], None)),
        )
        if operand is not None
    ]

    def fn(q_, k_, v_, qp_, kp_, *more):
        return base(q_, k_, v_, qp_, kp_, **{name: x for (name, _, _), x in zip(extras, more)})

    operands = tuple(operand for _, operand, _ in extras)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return fn(q, k, v, q_pos, kv_pos, *operands)

    q_spec = P(*policy.q)
    qp_spec = P(policy.q[0], policy.q[2])  # (B, Sq) follows q's batch/seq axes
    kp_spec = P(kv_spec[0], None)
    shard_fn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(q_spec, P(*kv_spec), P(*kv_spec), qp_spec, kp_spec)
        + tuple(spec for _, _, spec in extras),
        out_specs=q_spec,
        check_vma=False,
    )
    return shard_fn(q, k, v, q_pos, kv_pos, *operands)
