"""Pallas flash-attention kernel parity vs the XLA reference path
(reference analog: NKI kernel unit tests, test/unit/modules/kernels).

On CPU the kernels run in interpreter mode; semantics must match
ops/attention.py to float tolerance on every mask variant."""

import jax
import numpy as np
import pytest

import jax.numpy as jnp

from nxdi_tpu.ops.attention import attention_with_positions
from nxdi_tpu.ops.kernels import flash_attention_decode, flash_attention_prefill


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])
@pytest.mark.parametrize("window,chunk", [(None, None), (6, None), (None, 8)])
def test_prefill_kernel_matches_xla(H, KV, window, chunk):
    B, S, D = 2, 32, 16
    q = _rand((B, H, S, D), 0)
    k = _rand((B, KV, S, D), 1)
    v = _rand((B, KV, S, D), 2)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    expected = attention_with_positions(
        q, k, v, pos, pos, sliding_window=window, chunk_size=chunk
    )
    actual = flash_attention_prefill(
        q, k, v, pos, pos, sliding_window=window, chunk_size=chunk,
        block_q=8, block_k=8,
    )
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


def test_prefill_kernel_right_padded_positions():
    """Pad lanes carry positions past the true length; outputs at true
    positions must be identical to the XLA path."""
    B, H, KV, S, D = 1, 4, 2, 16, 8
    q, k, v = _rand((B, H, S, D)), _rand((B, KV, S, D), 1), _rand((B, KV, S, D), 2)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    expected = attention_with_positions(q, k, v, pos, pos)
    actual = flash_attention_prefill(q, k, v, pos, pos, block_q=4, block_k=4)
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])
def test_decode_kernel_matches_xla(H, KV):
    B, W, D = 2, 32, 16
    q = _rand((B, H, 1, D), 0)
    k = _rand((B, KV, W, D), 1)
    v = _rand((B, KV, W, D), 2)
    q_pos = jnp.array([[13], [7]], jnp.int32)
    kv_pos = jnp.tile(jnp.arange(W, dtype=jnp.int32), (B, 1))
    expected = attention_with_positions(q, k, v, q_pos, kv_pos)
    actual = flash_attention_decode(q, k, v, q_pos, kv_pos, block_k=8)
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


def test_decode_kernel_sliding_window():
    B, H, KV, W, D = 1, 4, 2, 32, 8
    q = _rand((B, H, 1, D), 3)
    k, v = _rand((B, KV, W, D), 4), _rand((B, KV, W, D), 5)
    q_pos = jnp.array([[20]], jnp.int32)
    kv_pos = jnp.tile(jnp.arange(W, dtype=jnp.int32), (B, 1))
    expected = attention_with_positions(q, k, v, q_pos, kv_pos, sliding_window=8)
    actual = flash_attention_decode(q, k, v, q_pos, kv_pos, sliding_window=8, block_k=8)
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


# ---------------------------------------------------------------------------
# Paged decode kernel
# ---------------------------------------------------------------------------

from nxdi_tpu.kvcache.kv_cache import BlockKVCacheSpec, BlockKVLayout  # noqa: E402
from nxdi_tpu.ops.kernels.flash_attention import paged_attention_decode  # noqa: E402

# the paged kernels take the WHOLE (L, slots, KV, D) pool and a layer index:
# every case builds a 3-layer pool and reads layer 1, against a reference
# computed on ``pool[1]`` alone
LAYERS, LAYER = 3, 1


def _gathered_window(pool_l, bt, bs):
    """One layer's (slots, KV, D) pool gathered through the block table:
    (B, KV, W, D) rows in table order and their positions, holes poisoned —
    the per-layer reference, independent of BlockKVLayout."""
    B, NB = bt.shape
    offs = jnp.arange(bs, dtype=jnp.int32)
    slots = (bt[:, :, None] * bs + offs[None, None, :]).reshape(B, -1)
    rows = jnp.swapaxes(jnp.take(pool_l, slots, axis=0, mode="clip"), 1, 2)
    W = NB * bs
    kv_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (B, W))
    valid = jnp.repeat(bt >= 0, bs, axis=1)
    return rows, jnp.where(valid, kv_pos, jnp.int32(2**30))


@pytest.mark.parametrize("D", [16, 128], ids=["d16", "d128"])
@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])
def test_paged_decode_kernel_matches_gathered_read(H, KV, D):
    """Kernel reading layer 1 of the pool through a scrambled block table
    (with holes) must equal the XLA gather path (BlockKVLayout.read at layer 1
    + attention), which must equal the plain gather of ``pool[1]``. At D=128
    the kernel takes its blocks through the pool's (L, slots * KV, D) view and
    scores all heads in one dot; at D=16 head by head."""
    B, block_size, num_blocks = 2, 8, 12
    total = num_blocks * block_size
    rng = np.random.default_rng(3)
    k_cache = jnp.asarray(rng.standard_normal((LAYERS, total, KV, D)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((LAYERS, total, KV, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, 1, D)), jnp.float32)
    # row 0: 3 live blocks (scrambled), 1 hole; row 1: 2 live blocks
    bt = jnp.array([[7, 2, 9, -1], [11, 0, -1, -1]], jnp.int32)
    q_pos = jnp.array([[21], [10]], jnp.int32)

    layout = BlockKVLayout(block_size=block_size)
    spec = BlockKVCacheSpec(
        num_layers=1, num_blocks=num_blocks, block_size=block_size,
        num_kv_heads=KV, head_dim=D, dtype="float32",
    )
    ci = {"block_table": bt, "layer_idx": jnp.int32(LAYER)}
    kk, vv, kv_pos = layout.read(k_cache, v_cache, ci, spec)
    kk_ref, pos_ref = _gathered_window(k_cache[LAYER], bt, block_size)
    vv_ref, _ = _gathered_window(v_cache[LAYER], bt, block_size)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kk_ref))
    np.testing.assert_array_equal(np.asarray(vv), np.asarray(vv_ref))
    np.testing.assert_array_equal(np.asarray(kv_pos), np.asarray(pos_ref))
    expected = attention_with_positions(q, kk_ref, vv_ref, q_pos, pos_ref)

    actual = paged_attention_decode(
        q, k_cache, v_cache, bt, q_pos, LAYER, block_size=block_size
    )
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=5e-5)


def test_paged_decode_kernel_rows_end_in_different_steps():
    """A 20-entry table is three steps of eight entries a row: rows of 1, 8,
    9, 17 and 20 live blocks end in their first, second and third step, so a
    block fetch started in one row's last live step is waited for in the next
    row's first, with the two buffers swapping on live steps only."""
    KV, G, D, bs, NB, blocks = 2, 2, 128, 8, 20, 64
    live = [1, 20, 8, 9, 17, 3]
    B = len(live)
    rng = np.random.default_rng(5)
    k_cache = jnp.asarray(rng.standard_normal((LAYERS, blocks * bs, KV, D)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((LAYERS, blocks * bs, KV, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, KV * G, 1, D)), jnp.float32)
    perm = rng.permutation(blocks)
    bt = np.full((B, NB), -1, np.int32)
    q_pos = np.zeros((B, 1), np.int32)
    at = 0
    for r, n in enumerate(live):
        bt[r, :n] = perm[at:at + n]
        at += n
        q_pos[r, 0] = (n - 1) * bs + int(rng.integers(0, bs))
    bt, q_pos = jnp.asarray(bt), jnp.asarray(q_pos)
    kk, kv_pos = _gathered_window(k_cache[LAYER], bt, bs)
    vv, _ = _gathered_window(v_cache[LAYER], bt, bs)
    expected = attention_with_positions(q, kk, vv, q_pos, kv_pos)
    actual = paged_attention_decode(q, k_cache, v_cache, bt, q_pos, LAYER, block_size=bs)
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=5e-5)


def test_paged_decode_kernel_scaled_fp8_folding():
    """k/v per-tensor scales fold into softmax scale / output normalization —
    must match the unscaled reference on a cache stored with inverse scales."""
    B, H, KV, D, block_size, num_blocks = 1, 4, 2, 16, 8, 6
    total = num_blocks * block_size
    rng = np.random.default_rng(4)
    k_raw = rng.standard_normal((LAYERS, total, KV, D)).astype(np.float32)
    v_raw = rng.standard_normal((LAYERS, total, KV, D)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, H, 1, D)), jnp.float32)
    bt = jnp.array([[3, 1, -1]], jnp.int32)
    q_pos = jnp.array([[13]], jnp.int32)
    k_scale, v_scale = 2.5, 0.75

    expected = paged_attention_decode(
        q, jnp.asarray(k_raw), jnp.asarray(v_raw), bt, q_pos, LAYER,
        block_size=block_size,
    )
    actual = paged_attention_decode(
        q,
        jnp.asarray(k_raw / k_scale),
        jnp.asarray(v_raw / v_scale),
        bt,
        q_pos,
        LAYER,
        block_size=block_size,
        k_scale=k_scale,
        v_scale=v_scale,
    )
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


# ---------------------------------------------------------------------------
# Fused deferred-write decode kernel
# ---------------------------------------------------------------------------

from nxdi_tpu.ops.attention import attention_two_part  # noqa: E402
from nxdi_tpu.ops.kernels import flash_attention_decode_fused  # noqa: E402


def _two_part_golden(q, kk, vv, kn, vn, q_pos, kv_pos, **kw):
    """The deferred-write decode semantics from models/base.py: old cache
    with this step's slot poisoned + the fresh row appended."""
    wpos = q_pos.astype(jnp.int32)
    hit = jnp.any(kv_pos[:, None, :] == wpos[:, :, None], axis=1)
    kv_pos_poisoned = jnp.where(hit, jnp.int32(2**30), kv_pos)
    return attention_two_part(
        q, kk, vv, kn, vn, q_pos, kv_pos_poisoned, wpos, **kw
    )


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])
@pytest.mark.parametrize("window,chunk", [(None, None), (8, None), (None, 8)])
def test_fused_decode_matches_two_part(H, KV, window, chunk):
    B, W, D = 2, 32, 16
    q = _rand((B, H, 1, D), 0)
    kk, vv = _rand((B, KV, W, D), 1), _rand((B, KV, W, D), 2)
    kn, vn = _rand((B, KV, 1, D), 3), _rand((B, KV, 1, D), 4)
    q_pos = jnp.array([[13], [7]], jnp.int32)
    kv_pos = jnp.tile(jnp.arange(W, dtype=jnp.int32), (B, 1))
    expected = _two_part_golden(
        q, kk, vv, kn, vn, q_pos, kv_pos, sliding_window=window, chunk_size=chunk
    )
    actual = flash_attention_decode_fused(
        q, kk, vv, kn, vn, q_pos, kv_pos,
        sliding_window=window, chunk_size=chunk, block_k=8,
    )
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


def test_fused_decode_position_zero():
    """Empty cache: only the fresh row is attendable."""
    B, H, KV, W, D = 1, 4, 2, 16, 8
    q = _rand((B, H, 1, D), 5)
    kk, vv = _rand((B, KV, W, D), 6), _rand((B, KV, W, D), 7)
    kn, vn = _rand((B, KV, 1, D), 8), _rand((B, KV, 1, D), 9)
    q_pos = jnp.zeros((B, 1), jnp.int32)
    kv_pos = jnp.tile(jnp.arange(W, dtype=jnp.int32), (B, 1))
    expected = _two_part_golden(q, kk, vv, kn, vn, q_pos, kv_pos)
    actual = flash_attention_decode_fused(q, kk, vv, kn, vn, q_pos, kv_pos, block_k=8)
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


def test_fused_decode_kv_len_bound():
    """kv_len statically truncates attended cache without slicing it."""
    B, H, KV, W, D = 1, 4, 2, 32, 8
    q = _rand((B, H, 1, D), 10)
    kk, vv = _rand((B, KV, W, D), 11), _rand((B, KV, W, D), 12)
    kn, vn = _rand((B, KV, 1, D), 13), _rand((B, KV, 1, D), 14)
    q_pos = jnp.array([[9]], jnp.int32)
    kv_pos = jnp.tile(jnp.arange(W, dtype=jnp.int32), (B, 1))
    expected = _two_part_golden(
        q, kk[:, :, :16], vv[:, :, :16], kn, vn, q_pos, kv_pos[:, :16]
    )
    actual = flash_attention_decode_fused(
        q, kk, vv, kn, vn, q_pos, kv_pos, block_k=8, kv_len=16
    )
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


# ---------------------------------------------------------------------------
# Paged prefill (prefix-cache / chunked-prefill CTE) kernel
# ---------------------------------------------------------------------------

from nxdi_tpu.ops.kernels import paged_attention_prefill  # noqa: E402


def _paged_pool(rng, total_slots, KV, D):
    k = jnp.asarray(rng.standard_normal((LAYERS, total_slots, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((LAYERS, total_slots, KV, D)), jnp.float32)
    return k, v


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])
def test_paged_prefill_matches_gathered_read(H, KV):
    """Bit-parity with the XLA path: materialized block-table gather +
    attention_with_positions over the gathered window."""
    rng = np.random.default_rng(0)
    B, Sq, D, bs = 2, 16, 16, 8
    total = 64
    k_cache, v_cache = _paged_pool(rng, total, KV, D)
    q = jnp.asarray(rng.standard_normal((B, H, Sq, D)), jnp.float32)
    # prefix of 2 blocks + the 2-block chunk; trailing entries unallocated
    bt = jnp.asarray([[3, 5, 0, 2, -1, -1], [7, 1, 6, 4, -1, -1]], jnp.int32)
    chunk_start = 2 * bs  # suffix begins after the 2-block prefix
    q_pos = chunk_start + jnp.tile(jnp.arange(Sq, dtype=jnp.int32), (B, 1))

    # golden: gather layer 1's table window, causal mask on logical positions
    kk, kv_pos = _gathered_window(k_cache[LAYER], bt, bs)
    vv, _ = _gathered_window(v_cache[LAYER], bt, bs)
    expected = attention_with_positions(q, kk, vv, q_pos, kv_pos)

    actual = paged_attention_prefill(
        q, k_cache, v_cache, bt, q_pos, LAYER, block_size=bs, block_q=8
    )
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


def test_paged_prefill_fp8_scale_folding():
    """k_scale folds into the softmax scale, v_scale into the output."""
    rng = np.random.default_rng(1)
    B, H, KV, Sq, D, bs = 1, 4, 2, 8, 8, 8
    k_cache, v_cache = _paged_pool(rng, 32, KV, D)
    q = jnp.asarray(rng.standard_normal((B, H, Sq, D)), jnp.float32)
    bt = jnp.asarray([[2, 0, -1, -1]], jnp.int32)
    q_pos = bs + jnp.tile(jnp.arange(Sq, dtype=jnp.int32), (B, 1))
    expected = paged_attention_prefill(
        q, k_cache * 2.0, v_cache * 0.5, bt, q_pos, LAYER, block_size=bs, block_q=8
    )
    actual = paged_attention_prefill(
        q, k_cache, v_cache, bt, q_pos, LAYER, block_size=bs, block_q=8,
        k_scale=2.0, v_scale=0.5,
    )
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


# ---------------------------------------------------------------------------
# The stacked pool and the layer index (decode + prefill)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_kernels_read_the_layer_the_index_names(kernel):
    """For L = 3 the kernel at layer l equals the per-layer reference on
    ``pool[l]`` — with the index a python int, and TRACED as a layer scan's
    xs hands it over (the serving path: the pool closed over whole, no slice
    of it taken)."""
    rng = np.random.default_rng(11)
    B, H, KV, D, bs = 2, 4, 2, 16, 8
    k_cache, v_cache = _paged_pool(rng, 64, KV, D)
    bt = jnp.asarray([[3, 5, 0, -1], [7, 1, -1, -1]], jnp.int32)
    if kernel == "decode":
        q = jnp.asarray(rng.standard_normal((B, H, 1, D)), jnp.float32)
        q_pos = jnp.array([[19], [12]], jnp.int32)
        call = lambda li: paged_attention_decode(  # noqa: E731
            q, k_cache, v_cache, bt, q_pos, li, block_size=bs
        )
    else:
        q = jnp.asarray(rng.standard_normal((B, H, 8, D)), jnp.float32)
        q_pos = bs + jnp.tile(jnp.arange(8, dtype=jnp.int32), (B, 1))
        call = lambda li: paged_attention_prefill(  # noqa: E731
            q, k_cache, v_cache, bt, q_pos, li, block_size=bs, block_q=8
        )

    def reference(layer):
        kk, kv_pos = _gathered_window(k_cache[layer], bt, bs)
        vv, _ = _gathered_window(v_cache[layer], bt, bs)
        return np.asarray(attention_with_positions(q, kk, vv, q_pos, kv_pos))

    refs = [reference(layer) for layer in range(LAYERS)]
    assert not np.allclose(refs[0], refs[1], atol=1e-3)  # layers do differ
    for layer in range(LAYERS):
        np.testing.assert_allclose(np.asarray(call(layer)), refs[layer], atol=2e-5)
    _, scanned = jax.lax.scan(
        lambda c, li: (c, call(li)), 0, jnp.arange(LAYERS, dtype=jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(scanned), np.stack(refs), atol=2e-5)


# -- PR 35: values of their own width, keys kept as lane tiles, a learned sink ----


@pytest.mark.parametrize("window", [None, 6], ids=["causal", "window"])
@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("Dv", [16, 8], ids=["same-width", "narrow-values"])
def test_prefill_kernel_value_width_and_sink(window, sink, Dv):
    """Values narrower than the keys, and one more softmax column a head whose
    probability is dropped: the running state starts from the sink."""
    B, H, KV, S, D = 2, 8, 2, 32, 16
    q, k, v = _rand((B, H, S, D), 0), _rand((B, KV, S, D), 1), _rand((B, KV, S, Dv), 2)
    sinks = _rand((H,), 3) * 2.0 if sink else None
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    expected = attention_with_positions(q, k, v, pos, pos, sliding_window=window, sink=sinks)
    actual = flash_attention_prefill(
        q, k, v, pos, pos, sliding_window=window, sink=sinks, block_q=8, block_k=8
    )
    assert actual.shape == (B, H, S, Dv)
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


def test_prefill_kernel_skips_blocks_behind_the_window():
    """A key block wholly behind the window of the query block's first row is
    not computed; the rows' results are the masked ones all the same, also
    where a query block's rows see no key of some block at all."""
    B, H, KV, S, D = 1, 4, 2, 64, 8
    q, k, v = _rand((B, H, S, D), 0), _rand((B, KV, S, D), 1), _rand((B, KV, S, D), 2)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    for window in (3, 8, 13):
        expected = attention_with_positions(q, k, v, pos, pos, sliding_window=window)
        actual = flash_attention_prefill(q, k, v, pos, pos, sliding_window=window, block_q=8, block_k=8)
        np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("D,W,Dv,tiles", [(16, 16, 8, 1), (128, 128, 128, 1), (256, 128, 128, 2)],
                         ids=["narrow-values", "lane-wide", "two-key-tiles"])
def test_paged_decode_kernel_value_width_and_key_tiles(D, W, Dv, tiles):
    """The value pool at its own width; a key row of two lane tiles kept as two
    pool rows, tile j of layer l at pool layer j * L + l (the last 64 lanes of
    the second tile zero, as a 192-wide key is padded): against the XLA
    attention over the layout's own gathered read."""
    from nxdi_tpu.kvcache.kv_cache import BlockKVCacheSpec, BlockKVLayout
    from nxdi_tpu.ops.kernels import paged_attention_decode, paged_decode_kernel_supported

    B, H, KV, L, bs, blocks = 3, 8, 2, 2, 8, 12
    real = 192 if tiles == 2 else D
    rng = np.random.default_rng(5)
    keys = rng.standard_normal((L, blocks * bs, KV, D)).astype(np.float32)
    keys[..., real:] = 0.0
    q = rng.standard_normal((B, H, 1, D)).astype(np.float32)
    q[..., real:] = 0.0
    k_pool = jnp.asarray(  # (tiles * L, slots, KV, W): tile-major over the layers
        np.concatenate([keys[..., j * W:(j + 1) * W] for j in range(tiles)], axis=0))
    v_pool = _rand((L, blocks * bs, KV, Dv), 6)
    table = jnp.asarray([[3, 1, 7, -1], [0, 2, -1, -1], [5, 4, 6, 8]], jnp.int32)
    q_pos = jnp.asarray([[19], [9], [31]], jnp.int32)
    assert paged_decode_kernel_supported(q.shape, k_pool.shape, bs, v_pool.shape)
    layout = BlockKVLayout(block_size=bs)
    spec = BlockKVCacheSpec(num_layers=L, num_blocks=blocks, block_size=bs, num_kv_heads=KV,
                            head_dim=W, dtype="float32", v_head_dim=Dv, key_tiles=tiles)
    for layer in range(L):
        ci = {"layer_idx": jnp.int32(layer), "block_table": table}
        kk, vv, kv_pos = layout.read(k_pool, v_pool, ci, spec)
        assert kk.shape[-1] == D and vv.shape[-1] == Dv
        expected = attention_with_positions(jnp.asarray(q), kk, vv, q_pos, kv_pos, scale=real ** -0.5)
        actual = paged_attention_decode(
            jnp.asarray(q), k_pool, v_pool, table, q_pos, jnp.int32(layer), block_size=bs,
            scale=real ** -0.5,
        )
        assert actual.shape == (B, H, 1, Dv)
        np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=3e-5)


def test_block_layout_writes_a_wide_key_as_lane_tiles():
    """``BlockKVLayout.update`` lands tile j of a key row in pool layer
    j * L + layer, and ``read`` puts the tiles side by side again."""
    from nxdi_tpu.kvcache.kv_cache import BlockKVCacheSpec, BlockKVLayout

    L, bs, blocks, KV, W, B = 2, 4, 3, 2, 128, 2
    spec = BlockKVCacheSpec(num_layers=L, num_blocks=blocks, block_size=bs, num_kv_heads=KV,
                            head_dim=W, dtype="float32", v_head_dim=8, key_tiles=2)
    assert spec.shape == (4, 12, KV, W) and spec.shape_v == (2, 12, KV, 8)
    k_pool, v_pool = jnp.zeros(spec.shape), jnp.zeros(spec.shape_v)
    k_new, v_new = _rand((B, KV, 1, 2 * W), 1), _rand((B, KV, 1, 8), 2)
    layout = BlockKVLayout(block_size=bs)
    ci = {"layer_idx": jnp.int32(1), "slot_mapping": jnp.asarray([[5], [-1]], jnp.int32),
          "block_table": jnp.asarray([[1, -1], [0, -1]], jnp.int32)}
    k_pool, v_pool = layout.update(k_pool, v_pool, k_new, v_new, ci, spec)
    np.testing.assert_array_equal(np.asarray(k_pool[1, 5]), np.asarray(k_new[0, :, 0, :W]))
    np.testing.assert_array_equal(np.asarray(k_pool[3, 5]), np.asarray(k_new[0, :, 0, W:]))
    assert float(jnp.abs(k_pool).sum()) == pytest.approx(float(jnp.abs(k_new[0]).sum()), rel=1e-6)
    kk, vv, _ = layout.read(k_pool, v_pool, ci, spec)
    np.testing.assert_array_equal(np.asarray(kk[0, :, 5 - 4]), np.asarray(k_new[0, :, 0]))


# -- PR 37: a block selection: the prefill kernel's mask operand, the decode kernel's compact table --


@pytest.mark.parametrize("S, select_block, bq, bk", [(64, 8, 16, 32), (128, 8, 32, 128), (64, 4, 8, 64)],
                         ids=["4-blocks-a-tile", "16-blocks-a-tile", "one-kv-tile"])
def test_prefill_kernel_reads_the_selected_blocks_alone(S, select_block, bq, bk):
    """A block mask a (kv head, query token): a key is attended iff its block is
    selected and it is not in the future. Whole kv tiles that no query of a q
    tile selected are skipped; the result is the masked softmax all the same."""
    from nxdi_tpu.ops.attention import grouped_attention

    B, H, KV, D = 2, 8, 2, 16
    q, k, v = _rand((B, H, S, D), 0), _rand((B, KV, S, D), 1), _rand((B, KV, S, D), 2)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    rng = np.random.default_rng(5)
    NB = S // select_block
    mask = rng.random((B, KV, S, NB)) < 0.3
    own = np.arange(S)[:, None] // select_block == np.arange(NB)[None, :]
    mask |= own[None, None]  # a query's own block, as the selection always holds it
    mask[:, :, : S // 2, NB // 2:] = False  # whole tiles nobody selected (and the future)
    mask |= own[None, None]
    block_mask = jnp.asarray(mask)
    actual = flash_attention_prefill(q, k, v, pos, pos, block_q=bq, block_k=bk, block_mask=block_mask)
    tokens = np.repeat(mask, select_block, axis=-1) & (np.arange(S)[None, :] <= np.arange(S)[:, None])
    expected = jnp.stack([  # one kv head's group at a time: the mask is the head's
        grouped_attention(q[:, g * 4:(g + 1) * 4], k[:, g:g + 1], v[:, g:g + 1], jnp.asarray(tokens[:, g]))
        for g in range(KV)], axis=1).reshape(B, H, S, D)
    np.testing.assert_allclose(np.asarray(actual), np.asarray(expected), atol=2e-5)
    dense = flash_attention_prefill(q, k, v, pos, pos, block_q=bq, block_k=bk)
    assert float(jnp.abs(dense - actual).max()) > 1e-2  # and it is not the dense result
    with pytest.raises(ValueError, match="block_mask"):
        flash_attention_prefill(q, k, v, pos, pos, block_q=bq, block_k=bk, block_mask=block_mask[:, :1])


def test_paged_decode_kernel_reads_a_compact_table_a_row_and_kv_head():
    """``block_select.decode_tables`` + the paged decode kernel AS IT IS, over
    the pool as one-head blocks: every (row, KV head) reads its own ``topk``
    blocks, chosen by scores over the index of compressed keys; rows under
    ``dense_len`` read their own table. Against the selection and the softmax
    written out in numpy."""
    from nxdi_tpu.ops import block_select

    cfg = block_select.BlockSelectConfig(kernel_size=4, kernel_stride=2, block_size=8, topk=6,
                                         init_blocks=1, window_size=16, dense_len=64)
    B, KV, G, D, NB = 3, 2, 4, 16, 16
    bs, H = cfg.block_size, KV * G
    rng = np.random.default_rng(11)
    positions = np.array([100, 37, 127])  # past dense_len, under it, the last position of a block
    keys = rng.standard_normal((B, KV, NB * bs, D)).astype(np.float32)
    values = rng.standard_normal((B, KV, NB * bs, D)).astype(np.float32)
    q = rng.standard_normal((B, KV, G, D)).astype(np.float32)
    table = rng.permutation(B * NB).reshape(B, NB).astype(np.int32)  # physical blocks, shuffled
    pool_k = np.zeros((1, B * NB * KV * bs, D), np.float32)
    pool_v = np.zeros_like(pool_k)
    for b in range(B):
        for g in range(KV):
            for n in range(NB):
                rows = (table[b, n] * KV + g) * bs + np.arange(bs)
                pool_k[0, rows] = keys[b, g, n * bs:(n + 1) * bs]
                pool_v[0, rows] = values[b, g, n * bs:(n + 1) * bs]
    kc = block_select.compress_keys(jnp.asarray(keys), cfg)  # (B, J, KV, D)
    scale = D ** -0.5
    tables, q_pos, read, live = block_select.decode_tables(
        jnp.asarray(q), kc, jnp.asarray(positions), jnp.asarray(table), scale, cfg)
    assert tables.shape == (B, KV, cfg.table_width) and list(np.asarray(live)) == [13, 5, 16]
    assert np.asarray(read).tolist() == [[6, 6], [5, 5], [6, 6]]
    head = jnp.arange(KV, dtype=jnp.int32)[None, :, None]
    entries = jnp.where(tables >= 0, tables * KV + head, -1).reshape(B * KV, -1)
    out = paged_attention_decode(
        jnp.asarray(q).reshape(B * KV, G, 1, D), jnp.asarray(pool_k)[:, :, None, :],
        jnp.asarray(pool_v)[:, :, None, :], entries, q_pos.reshape(B * KV, 1), jnp.int32(0),
        block_size=bs, scale=scale,
    ).reshape(B, KV, G, D)

    kc_np = np.asarray(kc)
    for b, t in enumerate(positions):
        cur = t // bs
        for g in range(KV):
            if t < cfg.dense_len:
                chosen = np.arange(cur + 1)
            else:
                whole = np.arange(kc_np.shape[1]) * cfg.kernel_stride + cfg.kernel_size - 1 <= t
                s = np.einsum("gd,jd->gj", q[b, g], kc_np[b, :, g]) * scale
                s = np.where(whole, s, -np.inf)
                p = np.exp(s - s.max(axis=-1, keepdims=True))
                mass = (p / p.sum(axis=-1, keepdims=True)).sum(axis=0)
                score = np.full(NB, -np.inf)
                for n in range(cur + 1):  # windows that share a position with block n
                    js = [j for j in range(len(mass)) if whole[j] and j * 2 + 3 >= n * bs and j * 2 <= n * bs + bs - 1]
                    score[n] = max((mass[j] for j in js), default=-np.inf)
                forced = [n for n in range(cur + 1) if n < 1 or n >= max(t - 16 + 1, 0) // bs]
                rest = sorted((n for n in range(cur + 1) if n not in forced), key=lambda n: -score[n])
                chosen = np.array(sorted(forced + rest[: cfg.topk - len(forced)]))
                assert len(chosen) == cfg.topk
                got = np.asarray(tables[b, g])
                assert got[got >= 0].tolist() == table[b, chosen].tolist(), (b, g)
            cols = np.concatenate([np.arange(n * bs, (n + 1) * bs) for n in chosen])
            cols = cols[cols <= t]
            a = np.einsum("gd,sd->gs", q[b, g], keys[b, g, cols]) * scale
            a = np.exp(a - a.max(axis=-1, keepdims=True))
            want = (a / a.sum(axis=-1, keepdims=True)) @ values[b, g, cols]
            np.testing.assert_allclose(np.asarray(out[b, g]), want, atol=2e-5)
