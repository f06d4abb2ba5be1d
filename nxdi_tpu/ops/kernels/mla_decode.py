"""Pallas kernel of the ABSORBED latent-attention decode over the paged pool.

Token generation of a model with Multi-head Latent Attention (ops/mla.py):
the query is already in the latent space (``q_lat = q_nope @ W_UK``), a cached
token is ONE row shared by all heads — its normed latent (``v`` pool, 512
wide) and its rotated rope key (``k`` pool, 64 values in a lane tile of 128) —
and per row of the batch

    s[h, t] = (q_lat[h] . c[t] + q_rot[h] . k_rot[t]) * scale
    o_lat[h] = softmax_t(s[h, :]) @ c

so a block of 128 cached tokens is read ONCE and serves every head twice: as
keys (one dot of all H query rows against it) and as values. ``W_UV`` and
``o_proj`` follow outside.

The copies are ``paged_attention_decode``'s (flash_attention.py
``_paged_block_chain``, PR 27), the same code: the pools stay in HBM
(``memory_space=ANY``), the layer is a prefetched scalar, a grid step fetches
the LIVE blocks of up to ``PAGED_DECODE_PAGES_PER_STEP`` table entries itself,
all in flight together and one live step ahead of the compute, and steps past
a row's position touch neither the pool nor the state. The compute is its
own because the scores take a term from each pool and the values are the
latent pool itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nxdi_tpu.ops.kernels import mode
from nxdi_tpu.ops.kernels.flash_attention import (
    PAGED_DECODE_PAGES_PER_STEP,
    _online_softmax_step,
    _paged_block_chain,
    _reset_softmax_state,
)


def mla_paged_decode_supported(q_lat_shape, k_pool_shape, v_pool_shape, block_size) -> bool:
    """``q_lat`` (B, H, r); pools (L, slots, 1, rope key in lane tiles) and
    (L, slots, 1, r)."""
    r = q_lat_shape[-1]
    slots, kv_heads, kd = k_pool_shape[1:]
    if kv_heads != 1 or v_pool_shape[1:] != (slots, 1, r) or slots % block_size:
        return False
    if mode.interpret():
        return True
    # the kernel copies (block_size, width) row blocks out of HBM itself:
    # Mosaic slices such a pool only at lane-tile widths
    return kd % 128 == 0 and r % 128 == 0 and block_size % 16 == 0


def _mla_paged_decode_kernel(
    li_ref, bt_ref, qp_ref, ql_ref, qr_ref, k_hbm, c_hbm, o_ref,
    m_ref, l_ref, acc_ref, k_buf, c_buf, sem, slot_ref,
    *, scale, n_rows, n_chunks, pages, block_size,
):
    """Grid (row, chunk of ``pages`` table entries); see the module docstring;
    ``_paged_block_chain`` brings the blocks of both pools."""
    b, c = pl.program_id(0), pl.program_id(1)
    layer = li_ref[0]

    def prepare():
        q_lat, q_rot = ql_ref[0], qr_ref[0]  # (H, r), (H, kd)
        token = jax.lax.broadcasted_iota(jnp.int32, (q_lat.shape[0], block_size), 1)
        return q_lat, q_rot, token

    def block(ctx, p, slot, q_pos):
        q_lat, q_rot, token = ctx
        k_rot = k_buf[slot, p]  # (block_size, kd)
        lat = c_buf[slot, p]  # (block_size, r): keys AND values
        contract = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            q_lat, lat, contract, preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            q_rot, k_rot, contract, preferred_element_type=jnp.float32
        )  # (H, block_size): every head against the one block
        kv_pos = (c * pages + p) * block_size + token
        _online_softmax_step(s * scale, kv_pos <= q_pos, m_ref, l_ref, acc_ref, lat)

    _paged_block_chain(
        b, c, bt_ref, qp_ref, layer, (k_hbm, c_hbm), (k_buf, c_buf), sem, slot_ref,
        n_rows=n_rows, n_chunks=n_chunks, pages=pages, block_size=block_size, rows=block_size,
        reset=lambda: _reset_softmax_state(m_ref, l_ref, acc_ref),
        prepare=prepare, block=block,
    )

    @pl.when(c == n_chunks - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


def mla_paged_decode(
    q_lat,  # (B, H, r) queries in the latent space
    q_rot,  # (B, H, rope_d) rotated rope queries
    k_pool,  # (L, slots, 1, kd >= rope_d) rotated rope keys, zero past rope_d
    c_pool,  # (L, slots, 1, r) normed latents
    block_table,  # (B, NB) int32 block ids in logical token order; <0 = hole
    q_pos,  # (B,) int32 decode positions
    layer_idx,  # scalar/1-elt int32: the layer of the pools to read
    *,
    block_size: int,
    scale: float,
):
    """``o_lat`` (B, H, r): softmax-weighted sum of each row's live latent
    rows, read through the block table — no gathered (B, W, r) copy in HBM."""
    B, H, r = q_lat.shape
    L, slots, _, kd = k_pool.shape
    NB = block_table.shape[1]
    if q_rot.shape[-1] < kd:  # the pool's rope rows are whole lane tiles
        q_rot = jnp.pad(q_rot, ((0, 0), (0, 0), (0, kd - q_rot.shape[-1])))
    bt = block_table.astype(jnp.int32)
    pages = min(PAGED_DECODE_PAGES_PER_STEP, NB)
    n_chunks = -(-NB // pages)
    if n_chunks * pages != NB:  # entries past the table are holes
        bt = jnp.pad(bt, ((0, 0), (0, n_chunks * pages - NB)), constant_values=-1)
    kernel = functools.partial(
        _mla_paged_decode_kernel, scale=scale, n_rows=B, n_chunks=n_chunks,
        pages=pages, block_size=block_size,
    )
    row = lambda b, c, *_: (b, 0, 0)  # noqa: E731
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, n_chunks),
            in_specs=[
                pl.BlockSpec((1, H, r), row), pl.BlockSpec((1, H, kd), row),
                pool_spec, pool_spec,
            ],
            out_specs=pl.BlockSpec((1, H, r), row),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),  # running max of one row, all heads
                pltpu.VMEM((H, 1), jnp.float32),  # running denominator
                pltpu.VMEM((H, r), jnp.float32),  # weighted-latent accumulator
                pltpu.VMEM((2, pages, block_size, kd), k_pool.dtype),  # [buffer, entry]
                pltpu.VMEM((2, pages, block_size, r), c_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2, pages)),  # [buffer, rope | latent, entry]
                pltpu.SMEM((1,), jnp.int32),  # the buffer the current step reads
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
        name="mla_paged_decode",
        interpret=mode.interpret(),
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1), bt, q_pos.astype(jnp.int32),
        q_lat, q_rot.astype(q_lat.dtype),
        k_pool.reshape(L, slots, kd), c_pool.reshape(L, slots, r),
    )


def sharded_mla_paged_decode_call(
    policy, q_lat, q_rot, k_pool, c_pool, block_table, q_pos, layer_idx,
    *, block_size, scale,
):
    """The kernel under GSPMD: queries shard over heads on the policy's head
    axis, the pools (one shared row a token), the table, the positions and the
    layer are replicated."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(mla_paged_decode, block_size=block_size, scale=scale)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return fn(q_lat, q_rot, k_pool, c_pool, block_table, q_pos, layer_idx)
    heads = P(None, policy.q[1], None)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(heads, heads, P(), P(), P(None, None), P(None), P()),
        out_specs=heads,
        check_vma=False,
    )(q_lat, q_rot, k_pool, c_pool, block_table, q_pos, layer_idx)
