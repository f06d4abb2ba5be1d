"""KV cache — functional, donation-friendly, layer-stacked.

The reference keeps K/V as per-layer ``nn.Parameter``s mutated in-graph
(modules/kvcache/kv_cache_manager.py:107 ``KVCacheManager``; shape
``(batch+pad, kv_heads/rank, max_len, head_dim)``). The TPU-native equivalent
is an explicit pytree carried through the jitted step and **donated**
(``donate_argnums``) so XLA aliases the buffers — zero-copy in steady state,
which is what the reference's parameter aliasing achieves.

Layout choice: one array per cache side, stacked over layers —
``(n_layers, batch, kv_heads, max_len, head_dim)`` — so the decoder runs as a
single ``lax.scan`` over layers. One compiled layer body instead of n_layers
unrolled copies: much faster XLA compiles at 70B scale, same runtime code.
How the cache meets that scan is the layout's (models/base.py
run_decoder_layers): the contiguous decode path reads the old stack and
commits the fresh rows once after the scan; the paged pool is the scan's
carry, written and read in place at (layer, slot); only the remaining paths
(contiguous prefill, ring, MLA) hand per-layer slices through as xs/ys.

Write semantics: exact-position scatter. New K/V for token at position p of
sequence b is written at [b, :, p, :]. Combined with position-derived causal
masks (ops/attention.py), right-padded prefill garbage is harmless: pad
positions are overwritten before any query can attend them (reference gets the
same effect from its scatter at position_ids, kv_cache_manager.py:374).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from nxdi_tpu.parallel.mesh import AXIS_MP


@dataclass(frozen=True)
class KVCacheSpec:
    """Static shape/dtype description of the cache (hashable; closed over by jit)."""

    num_layers: int
    batch_size: int
    num_kv_heads: int  # per-model padded count (parallel/gqa.py), NOT per-shard
    max_len: int
    head_dim: int
    dtype: str = "bfloat16"
    # fp8 KV quantization (reference: kv_cache_manager.py:642-692)
    quant_dtype: Optional[str] = None
    # MLA latent caches store DIFFERENT per-position widths in k and v
    # (k: rotated rope key, v: compressed normed kv latent); None = same as k
    v_head_dim: Optional[int] = None

    @property
    def store_dtype(self):
        from nxdi_tpu.config import to_jax_dtype

        return to_jax_dtype(self.quant_dtype or self.dtype)

    @property
    def compute_dtype(self):
        from nxdi_tpu.config import to_jax_dtype

        return to_jax_dtype(self.dtype)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.num_layers, self.batch_size, self.num_kv_heads, self.max_len, self.head_dim)

    @property
    def shape_v(self) -> Tuple[int, ...]:
        d = self.v_head_dim if self.v_head_dim is not None else self.head_dim
        return self.shape[:-1] + (d,)


def init_kv_cache(spec: KVCacheSpec) -> Dict[str, jax.Array]:
    """Zero-initialized cache pytree {'k': ..., 'v': ...}."""
    # distinct arrays: k and v are donated separately, sharing one buffer
    # would trip double-donation
    return {
        "k": jnp.zeros(spec.shape, dtype=spec.store_dtype),
        "v": jnp.zeros(spec.shape_v, dtype=spec.store_dtype),
    }


def kv_cache_partition_spec(tpu_config=None) -> Dict[str, P]:
    """Cache sharded over kv heads on the tp axis; with attention-DP the batch
    dim also shards over dp, with flash decoding the sequence dim shards over
    cp (parallel/policy.py maps the reference's DP/flash-decode KV managers)."""
    if tpu_config is not None:
        from nxdi_tpu.parallel.policy import kv_cache_partition_spec_for

        spec = kv_cache_partition_spec_for(tpu_config)
    else:
        spec = P(None, None, AXIS_MP, None, None)
    return {"k": spec, "v": spec}


@dataclass(frozen=True)
class BlockKVCacheSpec:
    """Paged layout: a flat pool of ``num_blocks * block_size`` token slots per
    layer (reference: modules/kvcache/block_kv_cache_manager.py:11 — vLLM-style
    ``(num_blocks, block_size, heads, dim)``; we keep slots flat so scatter and
    block-table gather are single-index ops)."""

    num_layers: int
    num_blocks: int
    block_size: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"
    quant_dtype: Optional[str] = None
    # the MLA latent pool: ``k`` rows are the rotated rope key (padded to a
    # lane tile, ops/mla.py), ``v`` rows the normed latent; None = same as k
    v_head_dim: Optional[int] = None
    # lane tiles a key row is kept as, each a pool row of ``head_dim`` in a
    # layer of its own (BlockKVLayout, KEY TILES): the key pool has
    # ``key_tiles * num_layers`` layers, the value pool ``num_layers``
    key_tiles: int = 1

    @property
    def store_dtype(self):
        from nxdi_tpu.config import to_jax_dtype

        return to_jax_dtype(self.quant_dtype or self.dtype)

    @property
    def compute_dtype(self):
        from nxdi_tpu.config import to_jax_dtype

        return to_jax_dtype(self.dtype)

    @property
    def total_slots(self) -> int:
        return self.num_blocks * self.block_size

    @property
    def shape(self) -> Tuple[int, ...]:
        layers = self.key_tiles * self.num_layers
        return (layers, self.total_slots, self.num_kv_heads, self.head_dim)

    @property
    def shape_v(self) -> Tuple[int, ...]:
        d = self.v_head_dim if self.v_head_dim is not None else self.head_dim
        return (self.num_layers,) + self.shape[1:-1] + (d,)


def init_block_kv_cache(spec: BlockKVCacheSpec) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros(spec.shape, dtype=spec.store_dtype),
        "v": jnp.zeros(spec.shape_v, dtype=spec.store_dtype),
    }


def block_kv_cache_partition_spec() -> Dict[str, P]:
    spec = P(None, None, AXIS_MP, None)
    return {"k": spec, "v": spec}


# ---------------------------------------------------------------------------
# Layout strategies — how new K/V lands in the cache and how decode reads it.
# The static analog of the reference's KVCacheManager subclass hierarchy
# (kv_cache_manager.py / block_kv_cache_manager.py / data_parallel_...): a
# frozen layout object is closed over by each jitted program.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ContiguousKVLayout:
    """(B_cache, KV, S, D) lines addressed by (seq_id, position).

    ``route_by_seq_id=True`` is continuous batching (reference:
    is_continuous_batching config + seq_ids plumbed through model_base.py
    forward :3367): batch row i reads/writes cache line ``seq_ids[i]`` instead
    of line i, so a CTE dispatch for one new request can land in any line while
    other lines keep decoding.

    ``k_scale``/``v_scale`` implement the reference's scaled fp8 KV cache
    (scale_mode="per_tensor", kv_cache_manager.py:642-692): values are divided
    by the scale before the fp8 store and re-multiplied after the load, so
    activations larger than the fp8 dynamic range survive. Static floats —
    part of the compiled program, like the reference's calibrated scale
    buffers baked into the traced graph.

    ``k_scales``/``v_scales`` are the per-layer PER-KEY / PER-CHANNEL scale
    buffers (reference: PER_KEY/PER_CHANNEL_SYMMETRIC scale ParameterLists,
    kv_cache_manager.py:642-667): nested tuples of shape (L, KV) (one scale
    per kv head) or (L, D) (one per head-dim channel), produced by
    kvcache.calibration. Inside the layer scan the active layer's scale row
    is selected by ``cache_inputs["layer_idx"]`` (the scan's arange xs);
    commit_rows broadcasts over the whole stack."""

    route_by_seq_id: bool = False
    k_scale: float = 1.0
    v_scale: float = 1.0
    k_scales: Optional[tuple] = None  # (L, KV) or (L, D) nested tuple
    v_scales: Optional[tuple] = None
    scale_axis: Optional[str] = None  # "key" | "channel" when *_scales set

    def _scale_for(self, which: str, cache_inputs, stacked: bool):
        """The active scale: a python float (per-tensor), or an array
        broadcastable against (B, KV, S, D) per-layer / (L, B, KV, S, D)
        stacked views."""
        scales = self.k_scales if which == "k" else self.v_scales
        if scales is None:
            return self.k_scale if which == "k" else self.v_scale
        arr = jnp.asarray(np.asarray(scales, dtype=np.float32))  # (L, KV)|(L, D)
        if self.scale_axis == "key":
            arr = arr[:, None, :, None, None]  # (L, 1, KV, 1, 1)
        else:  # channel
            arr = arr[:, None, None, None, :]  # (L, 1, 1, 1, D)
        if stacked:
            return arr
        li = (cache_inputs or {}).get("layer_idx")
        if li is None:
            raise NotImplementedError(
                "per-key/per-channel KV scales need the in-scan layer index; "
                "this execution path does not provide one"
            )
        return jnp.take(arr, li.astype(jnp.int32), axis=0, mode="clip")

    def has_array_scales(self) -> bool:
        return self.k_scales is not None or self.v_scales is not None

    @staticmethod
    def clip_to_store(x, store_dtype):
        """Saturate (and, for integer stores, ROUND) before the store cast:
        fp8 e4m3fn has NO inf — overflow becomes NaN — and an int8 astype
        truncates toward zero, so both need explicit handling (the
        reference's quantize_static_quant_activations clamps the same way)."""
        if jnp.issubdtype(jnp.dtype(store_dtype), jnp.integer):
            info = jnp.iinfo(store_dtype)
            return jnp.clip(jnp.round(x), info.min, info.max)
        lim = float(jnp.finfo(store_dtype).max)
        return jnp.clip(x, -lim, lim)

    def update(self, k_cache_l, v_cache_l, k_new, v_new, cache_inputs, spec):
        B = k_new.shape[0]
        # tree speculation writes nodes to DISTINCT slots while their rope
        # positions share depths (speculation/token_tree.py); everywhere else
        # write slot == rope position
        position_ids = cache_inputs.get("write_positions", cache_inputs["position_ids"])
        pos = jnp.where(position_ids < 0, k_cache_l.shape[2], position_ids)
        if self.route_by_seq_id:
            b_idx = cache_inputs["seq_ids"][:, None].astype(jnp.int32)
        else:
            b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
        store = k_cache_l.dtype
        if self.has_array_scales() or self.k_scale != 1.0:
            ks = self._scale_for("k", cache_inputs, stacked=False)
            k_new = self.clip_to_store(
                k_new.astype(jnp.float32) / ks, store
            ).astype(k_new.dtype)
        if self.has_array_scales() or self.v_scale != 1.0:
            vs = self._scale_for("v", cache_inputs, stacked=False)
            v_new = self.clip_to_store(
                v_new.astype(jnp.float32) / vs, store
            ).astype(v_new.dtype)
        if store != k_new.dtype:
            # narrowing store (incl. direct_cast fp8): saturate instead of
            # overflowing to NaN — and match the deferred path's round-trip
            # (models/base.py clips the attended fresh rows the same way)
            k_new = self.clip_to_store(k_new, store)
            v_new = self.clip_to_store(v_new, store)
        if (
            k_new.shape[2] > 1
            and cache_inputs.get("prefill_from_zero", False)
            and not self.route_by_seq_id
        ):
            # CTE fast path: by the context-encoding contract every row
            # writes positions [0, S_act) (right-pad lanes continue the
            # arange), so the write is ONE dynamic_update_slice at the
            # origin — XLA lowers the general positional write as a scatter
            # over B*S_act rows, the same pathology the decode commit kernel
            # killed (ops/kernels/kv_commit.py)
            k_cache_l = jax.lax.dynamic_update_slice(
                k_cache_l, k_new.astype(store), (0, 0, 0, 0)
            )
            v_cache_l = jax.lax.dynamic_update_slice(
                v_cache_l, v_new.astype(store), (0, 0, 0, 0)
            )
            return k_cache_l, v_cache_l
        k_vals = jnp.swapaxes(k_new, 1, 2).astype(store)  # (B, S_act, KV, D)
        v_vals = jnp.swapaxes(v_new, 1, 2).astype(store)
        k_cache_l = k_cache_l.at[b_idx, :, pos].set(k_vals, mode="drop")
        v_cache_l = v_cache_l.at[b_idx, :, pos].set(v_vals, mode="drop")
        return k_cache_l, v_cache_l

    def read(self, k_cache_l, v_cache_l, cache_inputs, spec):
        """Returns (kk, vv, kv_pos): (B, KV, W, D) x2 and (B, W) positions."""
        compute = spec.compute_dtype
        kk, vv = k_cache_l.astype(compute), v_cache_l.astype(compute)
        if self.has_array_scales() or self.k_scale != 1.0:
            kk = (kk * self._scale_for("k", cache_inputs, stacked=False)).astype(compute)
        if self.has_array_scales() or self.v_scale != 1.0:
            vv = (vv * self._scale_for("v", cache_inputs, stacked=False)).astype(compute)
        if self.route_by_seq_id:
            seq_ids = cache_inputs["seq_ids"].astype(jnp.int32)
            kk = jnp.take(kk, seq_ids, axis=0, mode="clip")
            vv = jnp.take(vv, seq_ids, axis=0, mode="clip")
        B, W = kk.shape[0], kk.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (B, W))
        return kk, vv, kv_pos

    def commit_rows(self, cache, k_rows, v_rows, cache_inputs, spec, policy=None):
        """Deferred-write commit: land the per-layer fresh K/V rows
        (L, B, KV, S_act, D) in the FULL stacked cache in one in-place op.

        The decode hot path cannot afford carrying cache slices through the
        layer scan as xs/ys — XLA round-trips the whole cache per layer
        (measured ~6x the pure-attention cost). Instead the scan emits only
        the new rows and attention reads the OLD cache with the written slots
        masked + fresh rows appended (models/base.py attention_block
        ``defer_write``); this commit is the single full-cache touch.

        Single-row commits (plain TKG decode) go through the Pallas in-place
        commit kernel (ops/kernels/kv_commit.py): XLA's TPU scatter lowering
        costs 8-14 ms at decode shapes (full-cache copies around the
        scatter), the kernel ~2 ms. Multi-row (speculation windows) and
        exotic shardings keep the jnp scatter."""
        position_ids = cache_inputs.get("write_positions", cache_inputs["position_ids"])
        S = cache["k"].shape[3]
        raw_pos = position_ids.astype(jnp.int32)  # (B, S_act); <0 = drop

        array_scales = self.has_array_scales()
        stacked_ks = self._scale_for("k", cache_inputs, stacked=True)
        stacked_vs = self._scale_for("v", cache_inputs, stacked=True)

        def scaled(rows, scale, store):
            if array_scales:
                return self.clip_to_store(
                    rows.astype(jnp.float32) / scale, store
                ).astype(store)
            if scale != 1.0:
                rows = rows / jnp.asarray(scale, rows.dtype)
            if store != rows.dtype:
                # saturate narrowing stores (incl. direct_cast), matching the
                # deferred attend's round-trip clip in models/base.py
                rows = self.clip_to_store(rows, store)
            return rows.astype(store)

        from nxdi_tpu.ops.kernels import kv_commit

        # Frozen-lane drops break the commit kernel's window contract: a
        # negative write position turns that lane's grid step into a
        # passthrough read-modify-write of its clipped (line, window) block,
        # and when a padding lane shares row 0's cache line (batch padding
        # duplicates row 0's seq_ids) the stale write-back clobbers row 0's
        # valid write landing in the same 128-slot window (kv_commit.py
        # CONTRACT). ``write_positions`` in the cache inputs is the static
        # trace-time marker that frozen lanes are possible — the multistep
        # scan and device-loop bodies inject it unconditionally — so those
        # commits keep the jnp scatter, whose mode='drop' is exact per
        # update.
        if "write_positions" not in cache_inputs and kv_commit.commit_rows_supported(
            cache["k"].shape, cache["v"].shape, k_rows.shape, v_rows.shape
        ):
            seq_ids = (
                cache_inputs["seq_ids"] if self.route_by_seq_id else None
            )
            if policy is not None:
                ck = policy.cache_kv
                pspec = P(None, ck[0], ck[1], ck[2], None)
            else:
                pspec = P(None, None, AXIS_MP, None, None)
            committed = kv_commit.sharded_commit_call(
                pspec,
                cache["k"],
                cache["v"],
                scaled(k_rows, stacked_ks, cache["k"].dtype),
                scaled(v_rows, stacked_vs, cache["v"].dtype),
                raw_pos,
                seq_ids,
            )
            if committed is not None:
                return {"k": committed[0], "v": committed[1]}

        pos = jnp.where(raw_pos < 0, S, raw_pos)  # OOB -> dropped by scatter
        B = pos.shape[0]
        if self.route_by_seq_id:
            b_idx = cache_inputs["seq_ids"].astype(jnp.int32)[:, None]
        else:
            b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]

        def put(cache_arr, rows, scale):
            vals = scaled(rows, scale, cache_arr.dtype).swapaxes(2, 3)  # (L,B,S,KV,D)

            def per_layer(cl, rl):  # (B,KV,S,D), (B,S,KV,D)
                return cl.at[b_idx, :, pos].set(rl, mode="drop")

            return jax.vmap(per_layer)(cache_arr, vals)

        return {
            "k": put(cache["k"], k_rows, stacked_ks),
            "v": put(cache["v"], v_rows, stacked_vs),
        }


@dataclass(frozen=True)
class BlockKVLayout:
    """Paged cache addressed by slot mappings (writes) and block tables (reads).

    reference: block_kv_cache_manager.py:268 ``_update_cache_into_block_layout``
    (slot-mapping scatter) and :150 ``_get_block_cache_and_reshape_bhsd``
    (active-block-table gather). Negative slots drop the write (padding lanes);
    the block-table gather returns rows in logical token order so kv positions
    are simply 0..W-1.

    ``update`` and ``read`` take the WHOLE pool ``(L, slots, KV, D)`` and the
    layer from ``cache_inputs["layer_idx"]``: the pool is one buffer that a
    step program writes at ``(layer, slot)`` in place and reads at
    ``(layer, slot)``; no per-layer slice of it is ever taken (a slice riding
    the layer scan as xs/ys costs whole-pool copies every step).

    KEY TILES: a key row wider than one 128-lane tile (mimo-v2's 192) is kept
    zero-padded as ``n`` whole lane tiles, one tile a pool row: the key pool
    is (n * L, slots, KV, 128) beside a value pool of L layers, tile ``j`` of
    layer ``l`` at pool layer ``j * L + l``. (As (L, slots, KV, n * 128) XLA
    tiles the pool by (KV, 128), and the paged kernel's (slots * KV, width)
    view of it is then a pool-sized copy every step, not a bitcast.) ``n`` is
    read from the two pools' layer counts; the caller pads keys and queries
    to ``n * 128``."""

    block_size: int
    k_scale: float = 1.0  # scaled fp8 store, see ContiguousKVLayout
    v_scale: float = 1.0

    @staticmethod
    def key_tiles(k_pool, v_pool) -> int:
        return k_pool.shape[0] // v_pool.shape[0]

    def update(self, k_pool, v_pool, k_new, v_new, cache_inputs, spec):
        # k_new (B, KV, S_act, D); slot_mapping (B, S_act) flat slot per token
        layer = cache_inputs["layer_idx"].astype(jnp.int32)
        slots = cache_inputs["slot_mapping"].astype(jnp.int32).reshape(-1)
        slots = jnp.where(slots < 0, k_pool.shape[1], slots)  # drop padding
        store = k_pool.dtype
        if self.k_scale != 1.0:
            k_new = k_new / jnp.asarray(self.k_scale, k_new.dtype)
        if self.v_scale != 1.0:
            v_new = v_new / jnp.asarray(self.v_scale, v_new.dtype)
        k_vals = jnp.swapaxes(k_new, 1, 2).astype(store)  # (B, S_act, KV, D)
        v_vals = jnp.swapaxes(v_new, 1, 2).astype(store)
        n = self.key_tiles(k_pool, v_pool)
        if n > 1:  # tile j of the row to pool layer j * L + layer
            rows = k_vals.reshape(-1, k_vals.shape[-2], n, k_pool.shape[-1])
            layers = layer + v_pool.shape[0] * jnp.arange(n, dtype=jnp.int32)
            k_pool = k_pool.at[layers[:, None], slots[None, :]].set(
                jnp.moveaxis(rows, 2, 0), mode="drop"
            )
        else:
            k_pool = k_pool.at[layer, slots].set(
                k_vals.reshape((-1,) + k_vals.shape[-2:]), mode="drop"
            )
        v_pool = v_pool.at[layer, slots].set(
            v_vals.reshape((-1,) + v_vals.shape[-2:]), mode="drop"
        )
        return k_pool, v_pool

    def read(self, k_pool, v_pool, cache_inputs, spec):
        # block_table (B, max_blocks) -> flat slots (B, max_blocks*block_size)
        layer = cache_inputs["layer_idx"].astype(jnp.int32)
        bt = cache_inputs["block_table"].astype(jnp.int32)
        B, NB = bt.shape
        offs = jnp.arange(self.block_size, dtype=jnp.int32)
        slots = (bt[:, :, None] * self.block_size + offs[None, None, :]).reshape(B, -1)
        # holes (negative table entries) clip onto slot 0; kv_pos hides them
        slots = jnp.clip(slots, 0, k_pool.shape[1] - 1)
        compute = spec.compute_dtype
        n, L = self.key_tiles(k_pool, v_pool), v_pool.shape[0]
        kk = jnp.concatenate(  # (B, W, KV, D): a row's lane tiles side by side
            [k_pool[layer + j * L, slots] for j in range(n)], axis=-1
        ).astype(compute)
        vv = v_pool[layer, slots].astype(compute)
        if self.k_scale != 1.0:
            kk = kk * jnp.asarray(self.k_scale, compute)
        if self.v_scale != 1.0:
            vv = vv * jnp.asarray(self.v_scale, compute)
        kk = jnp.swapaxes(kk, 1, 2)  # (B, KV, W, D)
        vv = jnp.swapaxes(vv, 1, 2)
        W = NB * self.block_size
        kv_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (B, W))
        # rows whose table entry is negative (unallocated) must not be attended
        valid = jnp.repeat(bt >= 0, self.block_size, axis=1)
        kv_pos = jnp.where(valid, kv_pos, jnp.int32(2**30))
        return kk, vv, kv_pos


@dataclass(frozen=True)
class WindowKVLayout:
    """Window-sized ring cache for sliding-window models: (B, KV, W, D) with
    position ``p`` living in slot ``p % W`` — cache memory is W slots instead
    of max_len (reference: per-layer window-sized cache shapes,
    kv_cache_manager.py:195-210 / gpt_oss_kv_cache_manager.py).

    Writes: only the LAST W real tokens land (a position is dropped if a
    later real token maps to the same slot); right-padding lanes continue the
    position arange past the true last token, so the keep-mask reads
    ``last_token_index`` from the cache inputs — without it a pad lane would
    alias (clobber) a live slot, which the full-length layout never had to
    care about.

    Reads (decode): slot ``s`` holds position ``p - ((p - s) mod W)`` for the
    FIRST query position ``p`` (single-token decode: the position; spec
    verify windows: the committed length); slots that would be negative
    (early decode) are pushed out of every causal mask. Linear speculation
    composes via ring over-provisioning (W = sliding_window + spec_len + 1,
    TpuConfig.window_ring_slots — see commit_rows); medusa/tree positions
    stay rejected at config level.
    """

    window: int
    route_by_seq_id: bool = False

    def update(self, k_cache_l, v_cache_l, k_new, v_new, cache_inputs, spec):
        B, S = cache_inputs["position_ids"].shape
        W = self.window
        pos = cache_inputs["position_ids"].astype(jnp.int32)
        lti = cache_inputs.get("last_token_index")
        last_real = (
            jnp.take_along_axis(pos, lti[:, None].astype(jnp.int32), axis=1)
            if lti is not None
            else pos[:, -1:]
        )  # (B, 1)
        keep = (pos <= last_real) & (pos > last_real - W)
        slot = jnp.where(keep, pos % W, W)  # W = dropped by the scatter
        if self.route_by_seq_id:
            b_idx = cache_inputs["seq_ids"][:, None].astype(jnp.int32)
        else:
            b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
        store = k_cache_l.dtype
        k_vals = jnp.swapaxes(k_new, 1, 2).astype(store)  # (B, S, KV, D)
        v_vals = jnp.swapaxes(v_new, 1, 2).astype(store)
        k_cache_l = k_cache_l.at[b_idx, :, slot].set(k_vals, mode="drop")
        v_cache_l = v_cache_l.at[b_idx, :, slot].set(v_vals, mode="drop")
        return k_cache_l, v_cache_l

    def read(self, k_cache_l, v_cache_l, cache_inputs, spec):
        compute = spec.compute_dtype
        kk, vv = k_cache_l.astype(compute), v_cache_l.astype(compute)
        if self.route_by_seq_id:
            seq_ids = cache_inputs["seq_ids"].astype(jnp.int32)
            kk = jnp.take(kk, seq_ids, axis=0, mode="clip")
            vv = jnp.take(vv, seq_ids, axis=0, mode="clip")
        W = self.window
        p = cache_inputs["position_ids"][:, :1].astype(jnp.int32)  # (B, 1)
        s = jnp.arange(W, dtype=jnp.int32)[None, :]
        kv_pos = p - ((p - s) % W)  # (B, W)
        kv_pos = jnp.where(kv_pos >= 0, kv_pos, jnp.int32(2 ** 30))
        return kk, vv, kv_pos

    def commit_rows(self, cache, k_rows, v_rows, cache_inputs, spec, policy=None):
        """Deferred-write commit into the ring: row for position ``p`` lands
        at slot ``p % W``. Correctness of attending the OLD ring before this
        commit: the stale row in that slot reports kv_pos == pos (ring math in
        ``read``), which the deferred poison mask excludes, and its true
        position pos - W is outside the window anyway.

        Multi-position windows (linear speculation verify) are safe because
        the ring is over-provisioned by the spec window
        (TpuConfig.window_ring_slots = sliding_window + spec_len + 1): every
        slot this commit clobbers previously held position ``p - W_ring``,
        which is below every future query's attention window, and a stale
        REJECTED row at position ``p_r`` resolves (for any later query
        ``q < p_r``) to inferred position ``p_r - W_ring`` — also out of
        window — until the true token at ``p_r`` overwrites it."""
        # write_positions override: negative = frozen lane, drop the write
        # (multistep scan / device-loop freeze semantics, same as the
        # contiguous layout's commit)
        position_ids = cache_inputs.get(
            "write_positions", cache_inputs["position_ids"]
        )
        W = self.window
        pos = position_ids.astype(jnp.int32)
        slots = jnp.where(pos >= 0, pos % W, jnp.int32(-1))  # neg = drop

        from nxdi_tpu.ops.kernels import kv_commit

        # same frozen-lane kernel hazard as the contiguous commit above:
        # write_positions present -> possible dropped lanes -> jnp scatter
        if "write_positions" not in cache_inputs and kv_commit.commit_rows_supported(
            cache["k"].shape, cache["v"].shape, k_rows.shape, v_rows.shape
        ):
            seq_ids = cache_inputs["seq_ids"] if self.route_by_seq_id else None
            if policy is not None:
                # carry the policy's seq-dim axis through so a seq-sharded
                # ring (never valid today — config rejects flash-decoding +
                # window_sized_kv — but specs mirror the full cache) trips
                # sharded_commit_call's bail instead of mis-sharding
                ck = policy.cache_kv
                pspec = P(None, ck[0], ck[1], ck[2], None)
            else:
                pspec = P(None, None, AXIS_MP, None, None)
            store = cache["k"].dtype
            committed = kv_commit.sharded_commit_call(
                pspec, cache["k"], cache["v"],
                k_rows.astype(store), v_rows.astype(store), slots, seq_ids,
            )
            if committed is not None:
                return {"k": committed[0], "v": committed[1]}

        B = slots.shape[0]
        sl = jnp.where(slots < 0, W, slots)  # OOB -> dropped by scatter
        if self.route_by_seq_id:
            b_idx = cache_inputs["seq_ids"].astype(jnp.int32)[:, None]
        else:
            b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]

        def put(cache_arr, rows):
            vals = rows.astype(cache_arr.dtype).swapaxes(2, 3)  # (L,B,1,KV,D)

            def per_layer(cl, rl):
                return cl.at[b_idx, :, sl].set(rl, mode="drop")

            return jax.vmap(per_layer)(cache_arr, vals)

        return {"k": put(cache["k"], k_rows), "v": put(cache["v"], v_rows)}


DEFAULT_KV_LAYOUT = ContiguousKVLayout()


def reset_kv_cache(cache: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Zero the cache (reference: model_base.py:3964 ``reset_kv_cache``)."""
    return jax.tree_util.tree_map(jnp.zeros_like, cache)


@partial(jax.jit, donate_argnums=(0,))
def _copy_kv_slots(cache, src_slots, dst_slots):
    out = dict(cache)
    for key in ("k", "v"):
        arr = cache[key]
        out[key] = arr.at[:, dst_slots].set(arr[:, src_slots])
    return out


def copy_kv_blocks(cache, src_blocks, dst_blocks, block_size: int):
    """Device-side KV block copy on the paged pool — the copy-on-write
    primitive: every slot of each ``src`` block is duplicated into the
    matching ``dst`` block across all layers for both k and v, in place
    (the cache is donated, as every forward already does). The serving
    engine calls this when a sequence must write into a block whose
    refcount says it is shared (prefix-cache partial blocks, ``n > 1``
    continuation forks) — the host-side table swap is
    ``BlockSpaceManager.cow_block``; this is the data movement."""
    src = np.asarray(src_blocks, dtype=np.int32).reshape(-1)
    dst = np.asarray(dst_blocks, dtype=np.int32).reshape(-1)
    if src.shape != dst.shape:
        raise ValueError(f"src/dst block counts differ: {src.shape} vs {dst.shape}")
    if src.size == 0:
        return cache
    offs = np.arange(block_size, dtype=np.int32)
    src_slots = (src[:, None] * block_size + offs[None, :]).reshape(-1)
    dst_slots = (dst[:, None] * block_size + offs[None, :]).reshape(-1)
    return _copy_kv_slots(cache, src_slots, dst_slots)


def _block_slots(blocks, block_size: int) -> np.ndarray:
    blocks = np.asarray(blocks, dtype=np.int32).reshape(-1)
    if blocks.size == 0:
        raise ValueError("empty block chain")
    if (blocks < 0).any():
        raise ValueError(f"negative block id in chain: {blocks.tolist()}")
    offs = np.arange(block_size, dtype=np.int32)
    return (blocks[:, None] * block_size + offs[None, :]).reshape(-1)


@jax.jit
def _gather_kv_slots(cache, slots):
    return cache["k"][:, slots], cache["v"][:, slots]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_kv_slots(cache, slots, k_rows, v_rows):
    out = dict(cache)
    out["k"] = cache["k"].at[:, slots].set(k_rows)
    out["v"] = cache["v"].at[:, slots].set(v_rows)
    return out


def export_kv_blocks(cache, blocks, block_size: int) -> Dict[str, np.ndarray]:
    """Gather a block chain's K/V contents to HOST numpy for the
    disaggregation handoff plane (serving/handoff.py): the prefill replica
    exports its finished chain, the wire carries it, and the decode replica
    scatters it via :func:`import_kv_blocks`. Same flat-slot addressing as
    :func:`copy_kv_blocks`; returns ``{"k", "v"}`` arrays of shape
    ``(num_layers, len(blocks) * block_size, num_kv_heads, head_dim)``."""
    slots = _block_slots(blocks, block_size)
    k, v = _gather_kv_slots(cache, slots)
    return {"k": np.asarray(jax.device_get(k)), "v": np.asarray(jax.device_get(v))}


def import_kv_blocks(cache, blocks, payload: Dict[str, np.ndarray], block_size: int):
    """Scatter an exported chain (:func:`export_kv_blocks` payload) into the
    receiver's block pool at ``blocks`` — length-checked and dtype/layout-
    validated against the receiver's cache format before any device work, so
    a mismatched wire payload fails loudly instead of corrupting the pool.
    The cache is donated like every other paged mutation."""
    slots = _block_slots(blocks, block_size)
    for side in ("k", "v"):
        rows = payload[side]
        want = cache[side].shape
        have = rows.shape
        if len(have) != len(want) or have[0] != want[0] or have[2:] != want[2:]:
            raise ValueError(
                f"handoff {side} layout mismatch: payload {tuple(have)} does "
                f"not address a cache of shape {tuple(want)} "
                "(layers/heads/head_dim must agree)"
            )
        if have[1] != slots.size:
            raise ValueError(
                f"handoff {side} length mismatch: payload carries {have[1]} "
                f"slots but the chain places {slots.size} "
                f"({len(np.asarray(blocks).reshape(-1))} blocks x {block_size})"
            )
        if jnp.dtype(rows.dtype) != jnp.dtype(cache[side].dtype):
            raise ValueError(
                f"handoff {side} dtype mismatch: payload {rows.dtype} vs "
                f"receiver cache {cache[side].dtype}"
            )
    return _scatter_kv_slots(cache, slots, payload["k"], payload["v"])
