"""Ragged paged-attention Pallas kernel (TPU).

ONE attention launch for a MIXED batch: prefill chunks (query_len = chunk)
and decode steps (query_len = 1) packed into a single flat token stream,
each token tagged with its (row, position) and every row reading K/V
through its slice of the paged block table — the "Ragged Paged Attention"
kernel shape (PAPERS.md) that lets the serving engine issue one dispatch
per step instead of separate CTE + TKG programs.

Relationship to the per-row kernels (flash_attention.py):
  - same cache addressing: the block table and the layer index ride scalar
    prefetch and the BlockSpec index maps pull (block_size, KV, D) blocks
    straight out of the (L, slots, KV, D) pool — no materialized
    (R, KV, W, D) gather in HBM, no per-layer slice of the pool.
  - same softmax state machine: `_online_softmax_step` is shared, and a
    fully-masked block update is an exact no-op on the running (m, l, acc)
    state (s == NEG_INF everywhere -> m_new == m_prev, corr == 1, p == 0).
    A packed token therefore sees EXACTLY the per-row kernel's update
    sequence — its own row's blocks in ascending order with identical
    operands — so the ragged output is bit-for-bit the per-row paged
    prefill/decode output for every real token (tests/unit/
    test_ragged_paged_attention.py pins this).
  - grid = (T/block_q, R*NB) with the row-x-block axis innermost
    (sequential) so the (m, l, acc) scratch persists across the whole
    row sweep for each q tile; a (row, block) step that cannot touch the
    tile (row outside the tile's [min, max] row range, or an unallocated
    table hole) is skipped under `pl.when`.

Padding tokens carry row_id == -1: no (row, block) step matches them, so
they finalize as zeros (l clamps to 1e-20) and the model-side gather never
reads them.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nxdi_tpu.ops.kernels import mode
from nxdi_tpu.ops.kernels.flash_attention import (
    NEG_INF,
    _online_softmax_step,
    _pick_block,
)


def ragged_paged_kernel_supported(q_shape, cache_shape, block_size) -> bool:
    """Same Mosaic envelope as the per-row paged prefill kernel, plus the
    packed layout's B == 1 (the batch dim is folded into the token stream).
    ``cache_shape`` is the stacked pool's (L, total_slots, KV, D)."""
    B, H, T, D = q_shape
    total_slots, KV = cache_shape[1], cache_shape[2]
    if B != 1 or H % KV or total_slots % block_size:
        return False
    if mode.interpret():
        return True
    return D % 8 == 0 and block_size % 128 == 0 and T % 8 == 0 and KV <= 16


def _ragged_kernel(
    li_ref, bt_ref, tmin_ref, tmax_ref, rid_ref, qp_ref, q_ref, k_ref, v_ref,
    o_ref, m_ref, l_ref, acc_ref,
    *, scale, v_scale, n_rows, n_blocks, KV, G, block_q, block_size,
    compute_dtype,
):
    del li_ref  # consumed by the cache index maps
    qi, j = pl.program_id(0), pl.program_id(1)
    rj = j // n_blocks  # the row this step serves
    bj = j % n_blocks  # the row's logical cache block
    bt = bt_ref[rj, bj]

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # skip table holes and rows entirely outside this q tile
    @pl.when((bt >= 0) & (rj >= tmin_ref[qi]) & (rj <= tmax_ref[qi]))
    def _():
        # packed token t belongs to row rid[t] at position qp[t]; kv col c
        # is LOGICAL position bj*block_size + c of row rj — a token attends
        # the (rj, c) pair iff it lives in that row and the position is
        # causal for it
        row_tile = rid_ref[:, 0]  # (block_q,)
        pos_tile = qp_ref[:, 0]
        kv_pos = bj * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 1
        )
        base_mask = (row_tile[:, None] == rj) & (kv_pos <= pos_tile[:, None])
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

    @pl.when(j == n_rows * n_blocks - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[0] = (
            (acc_ref[:] * v_scale / l[:, None])
            .reshape(KV, G, block_q, acc_ref.shape[-1])
            .astype(o_ref.dtype)
        )


def ragged_paged_attention(
    q,  # (1, H, T, D) — the packed mixed-batch queries
    k_cache,  # (L, total_slots, KV, D) — stacked pool, this step's rows written
    v_cache,  # (L, total_slots, KV, D)
    block_tables,  # (R, NB) int32 block ids per row in logical order; <0 = hole
    row_ids,  # (T,) int32 — owning row per packed token; -1 = padding
    q_pos,  # (T,) int32 — position within the row per packed token
    layer_idx,  # scalar/1-elt int32 — the layer of the stack to read
    *,
    block_size: int,
    scale: Optional[float] = None,
    k_scale: float = 1.0,
    v_scale: float = 1.0,
    block_q: int = 256,
):
    """Causal attention for a ragged mixed batch in one launch: the grid
    sweeps every (row, cache-block) pair for each packed-q tile, and the
    per-token (row, position) tags mask each tile down to exactly the
    per-row causal window — prefill chunks and single-token decode rows
    coexist in the same token stream. Per-tile row bounds (precomputed
    host-side-in-graph from ``row_ids``) skip the rows a tile cannot touch,
    so a tile over one row's chunk pays that row's blocks only."""
    B, H, T, D = q.shape
    assert B == 1, "ragged kernel takes the packed (1, H, T, D) layout"
    KV = k_cache.shape[2]
    G = H // KV
    R, NB = block_tables.shape
    scale = (D ** -0.5 if scale is None else scale) * k_scale
    compute_dtype = q.dtype
    # same VMEM bound as the per-row paged prefill kernel
    block_q = _pick_block(T, max(8, min(block_q, 4096 // max(H, 1))))
    nq = T // block_q

    qf = q.reshape(1, KV, G, T, D)
    bt = block_tables.astype(jnp.int32)
    rid = row_ids.astype(jnp.int32)
    qp = q_pos.astype(jnp.int32)
    li = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    # per-tile live row range for the block skip; an all-padding tile gets
    # an empty range (min > max) and touches no blocks at all
    rid2 = rid.reshape(nq, block_q)
    live = rid2 >= 0
    tile_min = jnp.min(jnp.where(live, rid2, jnp.int32(2 ** 30)), axis=1)
    tile_max = jnp.max(jnp.where(live, rid2, jnp.int32(-1)), axis=1)

    kernel = functools.partial(
        _ragged_kernel,
        scale=scale,
        v_scale=v_scale,
        n_rows=R,
        n_blocks=NB,
        KV=KV,
        G=G,
        block_q=block_q,
        block_size=block_size,
        compute_dtype=compute_dtype,
    )

    def cache_index(qi, j, li_ref, bt_ref, tmin_ref, tmax_ref):
        # holes/skipped steps clamp to block 0 — the kernel masks them out
        return li_ref[0], jnp.maximum(bt_ref[j // NB, j % NB], 0), 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nq, R * NB),
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda qi, j, *_: (qi, 0)),
            pl.BlockSpec((block_q, 1), lambda qi, j, *_: (qi, 0)),
            pl.BlockSpec(
                (1, KV, G, block_q, D), lambda qi, j, *_: (0, 0, 0, qi, 0)
            ),
            pl.BlockSpec((None, block_size, KV, D), cache_index),
            pl.BlockSpec((None, block_size, KV, D), cache_index),
        ],
        out_specs=pl.BlockSpec(
            (1, KV, G, block_q, D), lambda qi, j, *_: (0, 0, 0, qi, 0)
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
        out_shape=jax.ShapeDtypeStruct((1, KV, G, T, D), q.dtype),
        name="ragged_paged_attention",
        interpret=mode.interpret(),
    )(li, bt, tile_min, tile_max, rid[:, None], qp[:, None], qf, k_cache, v_cache)
    return out.reshape(1, H, T, D)


def sharded_ragged_paged_call(
    policy, q, k_cache, v_cache, block_tables, row_ids, q_pos, layer_idx,
    *, block_size, scale=None, k_scale=1.0, v_scale=1.0,
):
    """Ragged paged attention under GSPMD (see sharded_paged_prefill_call):
    the stacked pool and q shard over kv heads on tp; tables, token tags and
    the layer index are replicated."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        ragged_paged_attention,
        block_size=block_size,
        scale=scale,
        k_scale=k_scale,
        v_scale=v_scale,
    )
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return fn(q, k_cache, v_cache, block_tables, row_ids, q_pos, layer_idx)
    shard_fn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(*policy.q),
            P(None, None, policy.q[1], None),
            P(None, None, policy.q[1], None),
            P(None, None),
            P(None),
            P(None),
            P(),
        ),
        out_specs=P(*policy.q),
        check_vma=False,
    )
    return shard_fn(q, k_cache, v_cache, block_tables, row_ids, q_pos, layer_idx)
