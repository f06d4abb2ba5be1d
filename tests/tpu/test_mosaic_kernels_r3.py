"""Mosaic-compiled parity for the round-3 kernels on real TPU hardware:
the in-place KV commit kernel (kv_commit.py), the fused deferred-write
decode kernel, and the paged prefill (prefix/chunked CTE) kernel.

Run with:  NXDI_TPU_HW_TESTS=1 python -m pytest tests/tpu/ -q
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nxdi_tpu.ops.attention import attention_two_part, attention_with_positions
from nxdi_tpu.ops.kernels import (
    flash_attention_decode_fused,
    paged_attention_prefill,
)
from nxdi_tpu.ops.kernels.kv_commit import kv_commit_rows

pytestmark = pytest.mark.usefixtures("tpu")


def _rand(shape, seed=0, dtype=jnp.bfloat16):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape) * 0.5, dtype
    )


@pytest.mark.parametrize("D", [64, 128])
def test_mosaic_commit_kernel(D):
    L, B, KV, S = 4, 8, 4, 256
    rng = np.random.default_rng(0)
    kc = _rand((L, B, KV, S, D), 1)
    vc = _rand((L, B, KV, S, D), 2)
    kr = _rand((L, B, KV, 1, D), 3)
    vr = _rand((L, B, KV, 1, D), 4)
    pos = jnp.asarray(rng.integers(0, S, size=(B, 1)), jnp.int32)
    ok, ov = jax.jit(kv_commit_rows)(kc, vc, kr, vr, pos)
    ok, ov = np.asarray(ok), np.asarray(ov)

    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]

    def golden(cache, rows):
        vals = rows.swapaxes(2, 3)

        def per_layer(cl, rl):
            return cl.at[b_idx, :, pos].set(rl, mode="drop")

        return jax.vmap(per_layer)(cache, vals)

    np.testing.assert_array_equal(ok, np.asarray(golden(kc, kr)))
    np.testing.assert_array_equal(ov, np.asarray(golden(vc, vr)))


@pytest.mark.parametrize("D", [64, 128])
def test_mosaic_fused_decode(D):
    B, H, KV, W = 2, 8, 4, 256
    q = _rand((B, H, 1, D), 0)
    kk, vv = _rand((B, KV, W, D), 1), _rand((B, KV, W, D), 2)
    kn, vn = _rand((B, KV, 1, D), 3), _rand((B, KV, 1, D), 4)
    q_pos = jnp.array([[137], [55]], jnp.int32)
    kv_pos = jnp.tile(jnp.arange(W, dtype=jnp.int32), (B, 1))

    wpos = q_pos.astype(jnp.int32)
    hit = jnp.any(kv_pos[:, None, :] == wpos[:, :, None], axis=1)
    poisoned = jnp.where(hit, jnp.int32(2**30), kv_pos)
    expected = attention_two_part(q, kk, vv, kn, vn, q_pos, poisoned, wpos)
    actual = flash_attention_decode_fused(q, kk, vv, kn, vn, q_pos, kv_pos)
    np.testing.assert_allclose(
        np.asarray(actual, np.float32), np.asarray(expected, np.float32),
        atol=2e-2, rtol=2e-2,
    )


@pytest.mark.parametrize("D", [64, 128])
def test_mosaic_paged_prefill(D):
    B, H, KV, Sq, bs, NB = 2, 8, 4, 128, 128, 4
    total = 8 * bs
    rng = np.random.default_rng(0)
    k_cache = _rand((total, KV, D), 1)
    v_cache = _rand((total, KV, D), 2)
    q = _rand((B, H, Sq, D), 3)
    bt = jnp.asarray([[3, 5, -1, -1], [7, 1, -1, -1]], jnp.int32)
    q_pos = bs + jnp.tile(jnp.arange(Sq, dtype=jnp.int32), (B, 1))

    offs = jnp.arange(bs, dtype=jnp.int32)
    slots = (bt[:, :, None] * bs + offs[None, None, :]).reshape(B, -1)
    kk = jnp.swapaxes(jnp.take(k_cache, slots, axis=0, mode="clip"), 1, 2)
    vv = jnp.swapaxes(jnp.take(v_cache, slots, axis=0, mode="clip"), 1, 2)
    W = NB * bs
    kv_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (B, W))
    valid = jnp.repeat(bt >= 0, bs, axis=1)
    kv_pos = jnp.where(valid, kv_pos, jnp.int32(2**30))
    expected = attention_with_positions(q, kk, vv, q_pos, kv_pos)

    actual = paged_attention_prefill(
        q, k_cache, v_cache, bt, q_pos, block_size=bs, block_q=64
    )
    np.testing.assert_allclose(
        np.asarray(actual, np.float32), np.asarray(expected, np.float32),
        atol=2e-2, rtol=2e-2,
    )


@pytest.mark.parametrize("D", [64, 128])
def test_mosaic_paged_decode(D):
    """The restructured (KV-folded block) paged decode kernel at per-shard
    KV > 1 — the round-2 (block_size, 1, D) blocks violated Mosaic's tiling
    whenever a shard held more than one kv head."""
    from nxdi_tpu.ops.kernels import paged_attention_decode

    B, H, KV, bs, NB = 2, 8, 4, 128, 4
    total = 8 * bs
    k_cache = _rand((total, KV, D), 1)
    v_cache = _rand((total, KV, D), 2)
    q = _rand((B, H, 1, D), 3)
    bt = jnp.asarray([[3, 5, 2, -1], [7, 1, -1, -1]], jnp.int32)
    q_pos = jnp.asarray([[2 * bs + 17], [bs + 9]], jnp.int32)

    offs = jnp.arange(bs, dtype=jnp.int32)
    slots = (bt[:, :, None] * bs + offs[None, None, :]).reshape(B, -1)
    kk = jnp.swapaxes(jnp.take(k_cache, slots, axis=0, mode="clip"), 1, 2)
    vv = jnp.swapaxes(jnp.take(v_cache, slots, axis=0, mode="clip"), 1, 2)
    W = NB * bs
    kv_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (B, W))
    valid = jnp.repeat(bt >= 0, bs, axis=1)
    kv_pos = jnp.where(valid, kv_pos, jnp.int32(2**30))
    expected = attention_with_positions(q, kk, vv, q_pos, kv_pos)

    actual = paged_attention_decode(q, k_cache, v_cache, bt, q_pos, block_size=bs)
    np.testing.assert_allclose(
        np.asarray(actual, np.float32), np.asarray(expected, np.float32),
        atol=2e-2, rtol=2e-2,
    )
