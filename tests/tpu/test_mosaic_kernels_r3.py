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


# the paged kernels take the whole (L, slots, KV, D) pool and a layer index:
# a 3-layer pool read at layer 1, against the gather of ``pool[1]``
LAYERS, LAYER = 3, 1


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
    k_cache = _rand((LAYERS, total, KV, D), 1)
    v_cache = _rand((LAYERS, total, KV, D), 2)
    q = _rand((B, H, Sq, D), 3)
    bt = jnp.asarray([[3, 5, -1, -1], [7, 1, -1, -1]], jnp.int32)
    q_pos = bs + jnp.tile(jnp.arange(Sq, dtype=jnp.int32), (B, 1))

    offs = jnp.arange(bs, dtype=jnp.int32)
    slots = (bt[:, :, None] * bs + offs[None, None, :]).reshape(B, -1)
    kk = jnp.swapaxes(jnp.take(k_cache[LAYER], slots, axis=0, mode="clip"), 1, 2)
    vv = jnp.swapaxes(jnp.take(v_cache[LAYER], slots, axis=0, mode="clip"), 1, 2)
    W = NB * bs
    kv_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (B, W))
    valid = jnp.repeat(bt >= 0, bs, axis=1)
    kv_pos = jnp.where(valid, kv_pos, jnp.int32(2**30))
    expected = attention_with_positions(q, kk, vv, q_pos, kv_pos)

    actual = paged_attention_prefill(
        q, k_cache, v_cache, bt, q_pos, LAYER, block_size=bs, block_q=64
    )
    np.testing.assert_allclose(
        np.asarray(actual, np.float32), np.asarray(expected, np.float32),
        atol=2e-2, rtol=2e-2,
    )


@pytest.mark.parametrize("D", [64, 128])
def test_mosaic_paged_decode(D):
    """The (KV-folded block) paged decode kernel at per-shard KV > 1 — the
    round-2 (block_size, 1, D) blocks violated Mosaic's tiling whenever a
    shard held more than one kv head. The kernel fetches its blocks itself,
    one step ahead of the compute (rows of 3 and 2 live blocks here)."""
    from nxdi_tpu.ops.kernels import paged_attention_decode

    B, H, KV, bs, NB = 2, 8, 4, 128, 4
    total = 8 * bs
    k_cache = _rand((LAYERS, total, KV, D), 1)
    v_cache = _rand((LAYERS, total, KV, D), 2)
    q = _rand((B, H, 1, D), 3)
    bt = jnp.asarray([[3, 5, 2, -1], [7, 1, -1, -1]], jnp.int32)
    q_pos = jnp.asarray([[2 * bs + 17], [bs + 9]], jnp.int32)

    offs = jnp.arange(bs, dtype=jnp.int32)
    slots = (bt[:, :, None] * bs + offs[None, None, :]).reshape(B, -1)
    kk = jnp.swapaxes(jnp.take(k_cache[LAYER], slots, axis=0, mode="clip"), 1, 2)
    vv = jnp.swapaxes(jnp.take(v_cache[LAYER], slots, axis=0, mode="clip"), 1, 2)
    W = NB * bs
    kv_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (B, W))
    valid = jnp.repeat(bt >= 0, bs, axis=1)
    kv_pos = jnp.where(valid, kv_pos, jnp.int32(2**30))
    expected = attention_with_positions(q, kk, vv, q_pos, kv_pos)

    actual = paged_attention_decode(
        q, k_cache, v_cache, bt, q_pos, LAYER, block_size=bs
    )
    np.testing.assert_allclose(
        np.asarray(actual, np.float32), np.asarray(expected, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def _gathered(pool_l, bt, bs):
    """(B, KV, W, D) rows of one layer's (slots, KV, D) pool in table order,
    and their positions with the holes poisoned."""
    B, NB = bt.shape
    offs = jnp.arange(bs, dtype=jnp.int32)
    slots = (bt[:, :, None] * bs + offs[None, None, :]).reshape(B, -1)
    rows = jnp.swapaxes(jnp.take(pool_l, slots, axis=0, mode="clip"), 1, 2)
    pos = jnp.broadcast_to(jnp.arange(NB * bs, dtype=jnp.int32)[None, :], (B, NB * bs))
    return rows, jnp.where(jnp.repeat(bt >= 0, bs, axis=1), pos, jnp.int32(2**30))


def test_mosaic_paged_decode_rows_and_chunks():
    """The kernel's own block fetches on the chip: 16 rows of 1 to 20 live
    blocks behind a 20-entry table (three steps of eight entries a row, so
    rows end in their first, second and third step and a fetch started in
    one row is waited for in the next), GQA 16/2 at D=128, layer 1 of 3."""
    from nxdi_tpu.ops.kernels import paged_attention_decode

    B, H, KV, D, bs, NB, blocks = 16, 16, 2, 128, 128, 20, 256
    k_cache = _rand((LAYERS, blocks * bs, KV, D), 1)
    v_cache = _rand((LAYERS, blocks * bs, KV, D), 2)
    q = _rand((B, H, 1, D), 3)
    rng = np.random.default_rng(7)
    live = [1, 20, 8, 9, 3, 16, 17, 1, 7, 12, 2, 8, 20, 5, 1, 10]
    perm = rng.permutation(blocks)
    bt = np.full((B, NB), -1, np.int32)
    q_pos = np.zeros((B, 1), np.int32)
    at = 0
    for r, n in enumerate(live):
        bt[r, :n] = perm[at:at + n]
        at += n
        q_pos[r, 0] = (n - 1) * bs + int(rng.integers(0, bs))
    q_pos[0, 0] = 0  # a row whose only key is its own first token
    bt, q_pos = jnp.asarray(bt), jnp.asarray(q_pos)

    kk, kv_pos = _gathered(k_cache[LAYER], bt, bs)
    vv, _ = _gathered(v_cache[LAYER], bt, bs)
    expected = attention_with_positions(q, kk, vv, q_pos, kv_pos)
    actual = paged_attention_decode(
        q, k_cache, v_cache, bt, q_pos, LAYER, block_size=bs
    )
    np.testing.assert_allclose(
        np.asarray(actual, np.float32), np.asarray(expected, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_mosaic_ragged_paged():
    """Mixed dispatch's packed stream on the chip: a 256-token chunk behind a
    one-block prefix, two decode rows and a padded tail in one launch, layer
    1 of 3, each row against plain attention over its gathered window."""
    from nxdi_tpu.ops.kernels import ragged_paged_attention

    H, KV, D, bs, T, NB = 16, 2, 128, 128, 384, 4
    k_cache = _rand((LAYERS, 16 * bs, KV, D), 1)
    v_cache = _rand((LAYERS, 16 * bs, KV, D), 2)
    q = _rand((1, H, T, D), 3)
    rows = [
        (list(range(bs, bs + 256)), [3, 5, 9, -1]),
        ([2 * bs + 40], [7, 2, 11, -1]),
        ([17], [1, -1, -1, -1]),
    ]
    row_ids = np.full(T, -1, np.int32)
    q_pos = np.zeros(T, np.int32)
    spans, t = [], 0
    for r, (positions, _) in enumerate(rows):
        spans.append(np.arange(t, t + len(positions)))
        row_ids[t:t + len(positions)] = r
        q_pos[t:t + len(positions)] = positions
        t += len(positions)
    bt = jnp.asarray([table for _, table in rows], jnp.int32)

    out = ragged_paged_attention(
        q, k_cache, v_cache, bt, jnp.asarray(row_ids), jnp.asarray(q_pos), LAYER,
        block_size=bs,
    )
    for r, (positions, _) in enumerate(rows):
        kk, kv_pos = _gathered(k_cache[LAYER], bt[r:r + 1], bs)
        vv, _ = _gathered(v_cache[LAYER], bt[r:r + 1], bs)
        expected = attention_with_positions(
            q[:, :, spans[r], :], kk, vv, jnp.asarray([positions], jnp.int32), kv_pos
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :, spans[r], :], np.float32),
            np.asarray(expected, np.float32), atol=2e-2, rtol=2e-2,
        )
    assert np.all(np.asarray(out[:, :, t:, :], np.float32) == 0.0)
