"""The absorbed latent-attention decode: the Pallas kernel ``mla_paged_decode``
(interpret mode on the CPU) against the plain-XLA absorbed form over gathered
blocks, and the absorbed form against the non-absorbed one on the same cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nxdi_tpu.kvcache.kv_cache import (
    BlockKVCacheSpec,
    BlockKVLayout,
    copy_kv_blocks,
    export_kv_blocks,
    import_kv_blocks,
    init_block_kv_cache,
)
from nxdi_tpu.ops import mla
from nxdi_tpu.ops.kernels import mla_decode

BLOCK = 8


def _pools(rng, layers, blocks, kd, r, rope_d):
    """Random pools; the rope rows are zero past ``rope_d`` as the program
    writes them."""
    k = rng.standard_normal((layers, blocks * BLOCK, 1, kd)).astype(np.float32)
    k[..., rope_d:] = 0.0
    c = rng.standard_normal((layers, blocks * BLOCK, 1, r)).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(c)


def _xla(q_lat, q_rot, k_pool, c_pool, table, q_pos, layer, scale):
    """``absorbed_decode_xla`` over what ``BlockKVLayout.read`` gathers."""
    ci = {"block_table": table, "layer_idx": jnp.int32(layer)}
    spec = BlockKVCacheSpec(k_pool.shape[0], k_pool.shape[1] // BLOCK, BLOCK, 1,
                            k_pool.shape[-1], dtype="float32", v_head_dim=c_pool.shape[-1])
    k_all, c_all, kv_pos = BlockKVLayout(BLOCK).read(k_pool, c_pool, ci, spec)
    return mla.absorbed_decode_xla(q_lat, q_rot, k_all[:, 0], c_all[:, 0], q_pos, kv_pos, scale)


@pytest.mark.parametrize(
    "heads,r,rope_d,kd,table_width",
    [(4, 32, 8, 16, 5), (8, 128, 64, 128, 12), (4, 32, 8, 16, 8), (16, 64, 16, 128, 20)],
    ids=["narrow-5", "lane-12", "one-chunk-8", "three-chunks-20"],
)
def test_kernel_matches_the_xla_form(heads, r, rope_d, kd, table_width):
    """Layer 1 of a 3-layer pool, rows of unequal length, a hole in one row's
    table, a row with a single token, tables wider than one chunk of pages."""
    rng = np.random.default_rng(heads * 1000 + table_width)
    blocks = 4 * table_width
    k_pool, c_pool = _pools(rng, 3, blocks, kd, r, rope_d)
    B = 4
    table = rng.permutation(blocks)[: B * table_width].reshape(B, table_width).astype(np.int32)
    q_pos = np.array([table_width * BLOCK - 1, 3 * BLOCK + 2, 0, BLOCK], np.int32)
    table[1, 4:] = -1  # unallocated past the row's length
    table[0, 2] = -1  # a HOLE inside a live row: its block is never attended
    table[2, 1:] = -1
    q_lat = jnp.asarray(rng.standard_normal((B, heads, r)), jnp.float32)
    q_rot = jnp.asarray(rng.standard_normal((B, heads, rope_d)), jnp.float32)
    scale = (r + rope_d) ** -0.5
    want = _xla(q_lat, q_rot, k_pool, c_pool, jnp.asarray(table), jnp.asarray(q_pos), 1, scale)
    got = mla_decode.mla_paged_decode(
        q_lat, q_rot, k_pool, c_pool, jnp.asarray(table), jnp.asarray(q_pos), jnp.int32(1),
        block_size=BLOCK, scale=scale,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    other = mla_decode.mla_paged_decode(
        q_lat, q_rot, k_pool, c_pool, jnp.asarray(table), jnp.asarray(q_pos), jnp.int32(2),
        block_size=BLOCK, scale=scale,
    )
    assert not np.allclose(np.asarray(other), np.asarray(want), atol=1e-3), "the layer is read"


def test_kernel_is_named_after_its_entry_point():
    """``kernel.mla_decode_ms`` finds the kernel by this name in a trace."""
    rng = np.random.default_rng(0)
    k_pool, c_pool = _pools(rng, 1, 4, 16, 32, 8)
    args = (jnp.zeros((1, 4, 32)), jnp.zeros((1, 4, 8)), k_pool, c_pool,
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), jnp.int32(0))
    text = str(jax.make_jaxpr(
        lambda *a: mla_decode.mla_paged_decode(*a, block_size=BLOCK, scale=1.0))(*args))
    assert "mla_paged_decode" in text


@pytest.mark.parametrize("seed", [0, 1])
def test_absorbed_equals_non_absorbed_on_the_same_cache(seed):
    """q_nope through W_UK against latent rows, result through W_UV == the
    latent rows expanded through kv_b to per-head keys and values."""
    rng = np.random.default_rng(seed)
    H, nope, rope_d, vd, r, W, B = 4, 16, 8, 12, 32, 24, 3
    arch = mla.MLAArch(H, None, r, nope, rope_d, vd, (nope + rope_d) ** -0.5)
    kv_b = jnp.asarray(rng.standard_normal((r, H * (nope + vd))) * 0.2, jnp.float32)
    w_uk, w_uv = mla.absorbed_weights(arch, {"kv_b": {"w": kv_b}}, jnp.float32)
    q_nope = jnp.asarray(rng.standard_normal((B, H, nope)), jnp.float32)
    q_rot = jnp.asarray(rng.standard_normal((B, H, rope_d)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, W, r)), jnp.float32)
    k_rot = jnp.asarray(rng.standard_normal((B, W, rope_d)), jnp.float32)
    q_pos = jnp.asarray([W - 1, 5, 0], jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(W)[None], (B, W))

    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)
    o_lat = mla.absorbed_decode_xla(q_lat, q_rot, k_rot, c, q_pos, kv_pos, arch.softmax_scale)
    absorbed = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)

    kb = (c @ kv_b).reshape(B, W, H, nope + vd)
    k = jnp.concatenate([kb[..., :nope], jnp.broadcast_to(k_rot[:, :, None], (B, W, H, rope_d))], -1)
    q = jnp.concatenate([q_nope, q_rot], -1)
    s = jnp.einsum("bhd,bwhd->bhw", q, k) * arch.softmax_scale
    s = jnp.where(kv_pos[:, None, :] <= q_pos[:, None, None], s, -jnp.inf)
    expanded = jnp.einsum("bhw,bwhv->bhv", jax.nn.softmax(s, -1), kb[..., nope:])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), rtol=1e-4, atol=1e-5)


def test_latent_pool_spec_and_block_copies():
    """The paged latent pool is two arrays of unequal width; copy-on-write and
    the hand-off's export/import move both."""
    arch = mla.MLAArch(4, None, 512, 128, 64, 128, 1.0)
    assert mla.paged_latent_widths(arch) == (128, 512)
    spec = BlockKVCacheSpec(2, 6, BLOCK, 1, 16, dtype="float32", v_head_dim=32)
    assert spec.shape == (2, 6 * BLOCK, 1, 16) and spec.shape_v == (2, 6 * BLOCK, 1, 32)
    cache = init_block_kv_cache(spec)
    rng = np.random.default_rng(3)
    cache = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype) for k, v in cache.items()}
    before = {k: np.asarray(v) for k, v in cache.items()}
    copied = copy_kv_blocks(dict(cache), [1], [4], BLOCK)
    for side in ("k", "v"):
        got = np.asarray(copied[side])
        np.testing.assert_array_equal(got[:, 4 * BLOCK: 5 * BLOCK], before[side][:, BLOCK: 2 * BLOCK])
        np.testing.assert_array_equal(got[:, : 4 * BLOCK], before[side][:, : 4 * BLOCK])
    payload = export_kv_blocks(copied, [4, 0], BLOCK)
    assert payload["k"].shape == (2, 2 * BLOCK, 1, 16) and payload["v"].shape == (2, 2 * BLOCK, 1, 32)
    fresh = init_block_kv_cache(spec)
    landed = import_kv_blocks(fresh, [2, 3], payload, BLOCK)
    np.testing.assert_array_equal(
        np.asarray(landed["v"])[:, 2 * BLOCK: 3 * BLOCK], before["v"][:, BLOCK: 2 * BLOCK])
