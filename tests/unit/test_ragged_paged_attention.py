"""Ragged paged-attention kernel parity (ops/kernels/ragged_paged_attention).

The mixed-dispatch contract is BIT-FOR-BIT: a packed token must see exactly
the per-row paged kernel's online-softmax update sequence (its own row's
blocks in ascending order, every other (row, block) step an exact no-op on
its scratch rows), so each row's slice of the ragged output equals the
per-row ``paged_attention_prefill`` / ``paged_attention_decode`` output
with zero tolerance. Geometries per the mixed-dispatch issue: a row ending
exactly at the bucket edge, a single-token (decode) row, and an empty
padded tail. Two geometries hold only to float32 rounding (atol 1e-6) on the
CPU backend of jax 0.9, which rounds the two launch shapes differently; the
isolation they check fails at O(1), not at 1e-7."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nxdi_tpu.ops.kernels import (
    paged_attention_decode,
    paged_attention_prefill,
    ragged_paged_attention,
    ragged_paged_kernel_supported,
)


# the kernels take the WHOLE (L, slots, KV, D) pool and a layer index: every
# case builds a 3-layer pool and reads layer 1
LAYERS, LAYER = 3, 1


def _pool(rng, total_slots, KV, D):
    k = jnp.asarray(rng.standard_normal((LAYERS, total_slots, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((LAYERS, total_slots, KV, D)), jnp.float32)
    return k, v


def _pack(T, H, D, rows, rng):
    """rows: list of (positions list, table row list). Returns packed q,
    row_ids, q_pos (padding -1 / 0), plus per-row packed index slices."""
    q = jnp.asarray(rng.standard_normal((1, H, T, D)), jnp.float32)
    row_ids = np.full(T, -1, np.int32)
    q_pos = np.zeros(T, np.int32)
    spans = []
    t = 0
    for r, (positions, _table) in enumerate(rows):
        spans.append(list(range(t, t + len(positions))))
        for p in positions:
            row_ids[t] = r
            q_pos[t] = p
            t += 1
    assert t <= T
    bt = jnp.asarray([table for _, table in rows], jnp.int32)
    return q, jnp.asarray(row_ids), jnp.asarray(q_pos), bt, spans


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])
def test_ragged_mixed_batch_bitwise_per_row(H, KV):
    """Prefill chunk + decode row + short prefill + padded tail in ONE
    launch; every row's slice equals its per-row kernel to float32 rounding
    (a row leaking into another would be off by O(1); the CPU backend under
    jax 0.9 no longer rounds the two launch shapes bit-identically)."""
    rng = np.random.default_rng(0)
    T, D, bs = 16, 16, 8
    k_cache, v_cache = _pool(rng, 96, KV, D)
    rows = [
        (list(range(8, 14)), [3, 5, -1, -1]),  # chunk after a 1-block prefix
        ([21], [7, 2, 9, -1]),                 # decode step deep in its row
        (list(range(0, 5)), [1, -1, -1, -1]),  # fresh short prefill
    ]
    q, row_ids, q_pos, bt, spans = _pack(T, H, D, rows, rng)
    assert ragged_paged_kernel_supported(q.shape, k_cache.shape, bs)

    out = ragged_paged_attention(
        q, k_cache, v_cache, bt, row_ids, q_pos, LAYER, block_size=bs, block_q=8
    )

    for r, (positions, _) in enumerate(rows):
        idx = jnp.asarray(spans[r])
        q_row = q[:, :, idx, :]
        pos_row = jnp.asarray([positions], jnp.int32)
        if len(positions) == 1:
            expected = paged_attention_decode(
                q_row, k_cache, v_cache, bt[r : r + 1], pos_row, LAYER,
                block_size=bs,
            )
        else:
            expected = paged_attention_prefill(
                q_row, k_cache, v_cache, bt[r : r + 1], pos_row, LAYER,
                block_size=bs, block_q=8,
            )
        np.testing.assert_allclose(
            np.asarray(out[:, :, idx, :]), np.asarray(expected),
            rtol=0, atol=1e-6,
            err_msg=f"row {r} diverged from the per-row kernel",
        )
    # padded tail: finite zeros, never NaN (the model-side gather skips it,
    # but garbage must not poison reductions)
    pad = np.asarray(out[:, :, sum(len(p) for p, _ in rows):, :])
    assert np.all(np.isfinite(pad)) and np.all(pad == 0.0)


def test_ragged_row_at_bucket_edge():
    """A chunk filling the packed bucket exactly (no padding)."""
    rng = np.random.default_rng(1)
    H, KV, T, D, bs = 4, 2, 8, 8, 8
    k_cache, v_cache = _pool(rng, 64, KV, D)
    rows = [(list(range(8, 16)), [2, 6, -1])]
    q, row_ids, q_pos, bt, spans = _pack(T, H, D, rows, rng)
    out = ragged_paged_attention(
        q, k_cache, v_cache, bt, row_ids, q_pos, LAYER, block_size=bs, block_q=8
    )
    expected = paged_attention_prefill(
        q, k_cache, v_cache, bt, jnp.asarray([rows[0][0]], jnp.int32), LAYER,
        block_size=bs, block_q=8,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))


def test_ragged_all_decode_rows():
    """Pure decode packing: every row contributes one token."""
    rng = np.random.default_rng(2)
    H, KV, T, D, bs = 8, 2, 8, 16, 8
    k_cache, v_cache = _pool(rng, 64, KV, D)
    rows = [
        ([5], [4, -1]),
        ([11], [0, 3]),
        ([0], [7, -1]),
    ]
    q, row_ids, q_pos, bt, spans = _pack(T, H, D, rows, rng)
    out = ragged_paged_attention(
        q, k_cache, v_cache, bt, row_ids, q_pos, LAYER, block_size=bs, block_q=8
    )
    for r, (positions, _) in enumerate(rows):
        idx = jnp.asarray(spans[r])
        expected = paged_attention_decode(
            q[:, :, idx, :], k_cache, v_cache, bt[r : r + 1],
            jnp.asarray([positions], jnp.int32), LAYER, block_size=bs,
        )
        np.testing.assert_array_equal(
            np.asarray(out[:, :, idx, :]), np.asarray(expected)
        )


def test_ragged_empty_tail_is_inert():
    """A mostly-padding bucket (2 real tokens of 16): real tokens exact,
    the whole tail zeros — and the tail's all-padding tiles skip every
    block (empty per-tile row range), which this geometry exercises."""
    rng = np.random.default_rng(3)
    H, KV, T, D, bs = 4, 4, 16, 8, 8
    k_cache, v_cache = _pool(rng, 32, KV, D)
    rows = [([9], [1, 0]), ([3], [2, -1])]
    q, row_ids, q_pos, bt, spans = _pack(T, H, D, rows, rng)
    out = ragged_paged_attention(
        q, k_cache, v_cache, bt, row_ids, q_pos, LAYER, block_size=bs, block_q=4
    )
    for r, (positions, _) in enumerate(rows):
        idx = jnp.asarray(spans[r])
        expected = paged_attention_decode(
            q[:, :, idx, :], k_cache, v_cache, bt[r : r + 1],
            jnp.asarray([positions], jnp.int32), LAYER, block_size=bs,
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :, idx, :]), np.asarray(expected),
            rtol=0, atol=1e-6,
        )
    pad = np.asarray(out[:, :, 2:, :])
    assert np.all(pad == 0.0)


def test_ragged_fp8_scale_folding():
    """k/v per-tensor scales fold exactly like the per-row paged kernels."""
    rng = np.random.default_rng(4)
    H, KV, T, D, bs = 4, 2, 8, 8, 8
    k_cache, v_cache = _pool(rng, 32, KV, D)
    rows = [(list(range(0, 6)), [2, -1]), ([8], [3, 0])]
    q, row_ids, q_pos, bt, spans = _pack(T, H, D, rows, rng)
    expected = ragged_paged_attention(
        q, k_cache * 2.0, v_cache * 0.5, bt, row_ids, q_pos, LAYER,
        block_size=bs, block_q=8,
    )
    actual = ragged_paged_attention(
        q, k_cache, v_cache, bt, row_ids, q_pos, LAYER,
        block_size=bs, block_q=8, k_scale=2.0, v_scale=0.5,
    )
    np.testing.assert_allclose(
        np.asarray(actual), np.asarray(expected), atol=2e-5
    )


def test_ragged_reads_the_layer_the_index_names():
    """For L = 3 the packed launch at layer l equals, row by row, plain
    attention over ``pool[l]`` gathered through that row's table — with the
    index a python int and TRACED as a layer scan's xs hands it over."""
    from nxdi_tpu.ops.attention import attention_with_positions

    rng = np.random.default_rng(5)
    H, KV, T, D, bs = 4, 2, 16, 16, 8
    k_cache, v_cache = _pool(rng, 64, KV, D)
    rows = [(list(range(8, 14)), [3, 5, -1]), ([17], [6, 2, 0]), ([2], [1, -1, -1])]
    q, row_ids, q_pos, bt, spans = _pack(T, H, D, rows, rng)

    def call(li):
        return ragged_paged_attention(
            q, k_cache, v_cache, bt, row_ids, q_pos, li, block_size=bs, block_q=8
        )

    def reference(layer):
        out = np.zeros((1, H, T, D), np.float32)
        offs = np.arange(bs)
        for r, (positions, table) in enumerate(rows):
            table = np.asarray(table)
            slots = (np.maximum(table, 0)[:, None] * bs + offs[None, :]).reshape(-1)
            kk = jnp.swapaxes(k_cache[layer][slots], 0, 1)[None]  # (1, KV, W, D)
            vv = jnp.swapaxes(v_cache[layer][slots], 0, 1)[None]
            kv_pos = np.where(
                np.repeat(table >= 0, bs), np.arange(slots.size), 2**30
            ).astype(np.int32)[None]
            idx = np.asarray(spans[r])
            out[:, :, idx, :] = np.asarray(attention_with_positions(
                q[:, :, idx, :], kk, vv, jnp.asarray([positions], jnp.int32),
                jnp.asarray(kv_pos),
            ))
        return out

    refs = [reference(layer) for layer in range(LAYERS)]
    assert not np.allclose(refs[0], refs[1], atol=1e-3)  # layers do differ
    for layer in range(LAYERS):
        np.testing.assert_allclose(np.asarray(call(layer)), refs[layer], atol=2e-5)
    _, scanned = jax.lax.scan(
        lambda c, li: (c, call(li)), 0, jnp.arange(LAYERS, dtype=jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(scanned), np.stack(refs), atol=2e-5)
