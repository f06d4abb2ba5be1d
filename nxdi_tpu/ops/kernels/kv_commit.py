"""Pallas KV-cache commit kernel — in-place decode-row writes without XLA scatter.

Why this exists: the deferred-write decode path (models/base.py
``defer_write`` + kvcache/kv_cache.py ``commit_rows``) ends the step with one
scatter of the fresh K/V rows into the layer-stacked cache. XLA's TPU scatter
lowering is catastrophically slow for this shape: profiled 8-14 ms to land
512 KB of rows in a 1 GB cache (copy.39/copy.40 in the decode trace — full
cache copies inserted around the scatter), ~55% of the whole decode step. The
reference never meets this problem because its caches are torch Parameters
mutated in place by the runtime (kv_cache_manager.py:374 ``update_cache``);
this kernel is the TPU-native equivalent of that in-place write:
``input_output_aliases`` pins the outputs to the cache buffers and the grid
touches ONLY the 128-slot window holding each written row.

Layout detail (the part that makes it actually in-place): XLA's preferred
cache layout for the decode program is S-minor ({3,4,2,1,0} — sequence
contiguous, the "transposed-K" storage the reference also favors for TKG,
kv_cache_manager.py transposed option). A Pallas operand is always row-major,
so the kernel takes the cache through a logical (L, B, KV, D, S) TRANSPOSED
view: inside a program whose cache value already sits in the S-minor layout,
``jnp.swapaxes(cache, 3, 4)`` is a layout-preserving bitcast — no copy — and
the kernel's row-major view is byte-identical to the surrounding program's
preferred layout. Committing through the untransposed view instead costs 4
full-cache relayout copies (~21 ms, measured).

Semantics (matches ContiguousKVLayout.commit_rows jnp path bit-for-bit for
T == 1 under the contract below):
  - slot ``slots[b, 0]`` of cache line ``line(b)`` receives ``rows[:, b, :, 0]``
  - ``line(b) = seq_ids[b]`` under continuous batching else ``b``
  - out-of-range slots or seq_ids drop the row (best-effort; see contract)
  - duplicate (line, slot) pairs across batch rows only come from SPMD
    padding lanes repeating row 0 with identical values, so any write order
    yields the same bytes.

CONTRACT: each grid step read-modify-writes the whole 128-slot window around
its row, so two steps whose (line, window) collide with DIFFERENT contents
race (a dropped lane's passthrough write-back can clobber a valid write that
landed in the same window between its read and its write). The engaged paths
keep collisions value-identical or impossible:
  - routed (continuous batching): seq_ids are validated in-range host-side
    (model_wrapper._layout_inputs raises) and distinct except for padding
    lanes that repeat row 0's write verbatim;
  - non-routed: each lane only touches its own cache line, so a dropped
    (negative-slot) lane's write-back cannot overlap another lane's window.

T > 1 (speculation windows) stays on the jnp scatter path: adjacent
positions share an aligned window within one line, exactly the racing
pattern above.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nxdi_tpu.ops.kernels import mode

_WIN = 128  # lane-aligned slot window per write (S is the minor dim)


def commit_rows_supported(k_cache_shape, v_cache_shape, k_rows_shape, v_rows_shape) -> bool:
    """caches (L, B_cache, KV, S, D/Dv); rows (L, B, KV, T, D/Dv). T must be 1."""
    if any(
        len(s) != 5
        for s in (k_cache_shape, v_cache_shape, k_rows_shape, v_rows_shape)
    ):
        return False
    L, B_cache, KV, S, D = k_cache_shape
    Dv = v_cache_shape[4]
    if v_cache_shape != (L, B_cache, KV, S, Dv):
        return False
    if k_rows_shape[0] != L or k_rows_shape[2] != KV or k_rows_shape[4] != D:
        return False
    if v_rows_shape != k_rows_shape[:4] + (Dv,):
        return False
    if k_rows_shape[3] != 1:
        return False
    if mode.interpret():
        return True
    return S % _WIN == 0 and D % 8 == 0 and Dv % 8 == 0


def _commit_kernel(
    slots_ref, lines_ref, k_rows, v_rows, k_in, v_in, k_out, v_out, *, S, B_cache
):
    b = pl.program_id(0)
    slot = slots_ref[b, 0]
    line = lines_ref[b]
    # out-of-range seq_ids DROP the write (matching the jnp scatter's
    # mode='drop') — the index map clips them onto line 0 for addressing only
    valid = (slot >= 0) & (slot < S) & (line >= 0) & (line < B_cache)
    lane = slot % _WIN

    def put(out_ref, rows_ref, in_ref):
        # window-slot index along the minor S axis of the transposed view
        win = jax.lax.broadcasted_iota(jnp.int32, in_ref.shape, 4)
        out_ref[:] = jnp.where((win == lane) & valid, rows_ref[:], in_ref[:])

    put(k_out, k_rows, k_in)
    put(v_out, v_rows, v_in)


def kv_commit_rows(
    k_cache,  # (L, B_cache, KV, S, D) store dtype
    v_cache,  # (L, B_cache, KV, S, Dv)
    k_rows,  # (L, B, KV, 1, D) store dtype (caller scales/casts)
    v_rows,  # (L, B, KV, 1, Dv)
    slots,  # (B, 1) int32 target slots; <0 or >=S drops the write
    seq_ids: Optional[jax.Array] = None,  # (B,) cache-line routing
):
    """In-place commit of one fresh K/V row per batch line into the
    layer-stacked cache. Grid (B,); each step read-modify-writes the
    (L, KV, D, 128) window holding the target slot through aliased outputs,
    on the S-minor transposed view (see module docstring)."""
    L, B_cache, KV, S, D = k_cache.shape
    Dv = v_cache.shape[4]
    B = slots.shape[0]
    slots = slots.astype(jnp.int32)
    if seq_ids is None:
        lines = jnp.arange(B, dtype=jnp.int32)
    else:
        lines = seq_ids.astype(jnp.int32)  # raw: kernel drops out-of-range

    # bitcast-transpose to the S-minor view (free inside a program whose
    # cache already carries the S-minor layout)
    k_t = jnp.swapaxes(k_cache, 3, 4)  # (L, B_cache, KV, D, S)
    v_t = jnp.swapaxes(v_cache, 3, 4)
    kr_t = jnp.swapaxes(k_rows, 3, 4)  # (L, B, KV, D, 1)
    vr_t = jnp.swapaxes(v_rows, 3, 4)

    # tile the layer dim so in/out + double-buffered blocks fit scoped VMEM
    # (~16 MB): k+v, in+out, 2x pipelining = 8 copies of the block in flight
    block_bytes = KV * max(D, Dv) * _WIN * jnp.dtype(k_cache.dtype).itemsize
    budget = 8 * 1024 * 1024
    l_blk = 1
    for cand in range(L, 0, -1):
        if L % cand == 0 and 8 * cand * block_bytes <= budget:
            l_blk = cand
            break

    def cache_index(b, lt, slots_ref, lines_ref):
        slot = jnp.clip(slots_ref[b, 0], 0, S - 1)
        line = jnp.clip(lines_ref[b], 0, B_cache - 1)
        return lt, line, 0, 0, slot // _WIN

    def rows_index(b, lt, slots_ref, lines_ref):
        return lt, b, 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, L // l_blk),
        in_specs=[
            pl.BlockSpec((l_blk, 1, KV, D, 1), rows_index),
            pl.BlockSpec((l_blk, 1, KV, Dv, 1), rows_index),
            pl.BlockSpec((l_blk, 1, KV, D, _WIN), cache_index),
            pl.BlockSpec((l_blk, 1, KV, Dv, _WIN), cache_index),
        ],
        out_specs=[
            pl.BlockSpec((l_blk, 1, KV, D, _WIN), cache_index),
            pl.BlockSpec((l_blk, 1, KV, Dv, _WIN), cache_index),
        ],
    )
    out_k, out_v = pl.pallas_call(
        functools.partial(_commit_kernel, S=S, B_cache=B_cache),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_t.shape, k_t.dtype),
            jax.ShapeDtypeStruct(v_t.shape, v_t.dtype),
        ],
        # inputs are (slots, lines, k_rows, v_rows, k_cache, v_cache)
        input_output_aliases={4: 0, 5: 1},
        name="kv_commit_rows",
        interpret=mode.interpret(),
    )(slots, lines, kr_t, vr_t, k_t, v_t)
    return jnp.swapaxes(out_k, 3, 4), jnp.swapaxes(out_v, 3, 4)


def sharded_commit_call(
    cache_pspec,  # PartitionSpec of the stacked cache (L, B, KV, S, D)
    k_cache, v_cache, k_rows, v_rows, slots, seq_ids=None,
):
    """Commit under GSPMD: shard_map mirroring the cache sharding (kv heads on
    tp, optionally batch on dp). Returns None when the cache's sequence dim is
    sharded (flash-decoding KV-S layout) — slots are global positions the
    local shard can't address — and the caller falls back to the jnp scatter.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return kv_commit_rows(k_cache, v_cache, k_rows, v_rows, slots, seq_ids)
    axes = tuple(cache_pspec) + (None,) * (5 - len(tuple(cache_pspec)))
    if axes[3] is not None:
        return None  # sequence-sharded cache: global slots, local shards
    if axes[1] is not None and seq_ids is not None:
        return None  # batch-sharded + seq-id routing would cross shards
    rows_spec = P(axes[0], axes[1], axes[2], None, None)
    shard_fn = jax.shard_map(
        kv_commit_rows,
        mesh=mesh,
        in_specs=(
            P(*axes),
            P(*axes),
            rows_spec,
            rows_spec,
            P(axes[1], None),
            None if seq_ids is None else P(axes[1]),
        ),
        out_specs=(P(*axes), P(*axes)),
        check_vma=False,
    )
    return shard_fn(k_cache, v_cache, k_rows, v_rows, slots, seq_ids)
