"""KV cache tests (reference analog: test/unit/modules/kvcache)."""

import jax
import jax.numpy as jnp
import numpy as np

from nxdi_tpu.kvcache.kv_cache import (
    BlockKVLayout,
    ContiguousKVLayout,
    KVCacheSpec,
    init_kv_cache,
    reset_kv_cache,
)

LAYOUT = ContiguousKVLayout()


def update_layer_cache(kl, vl, k_new, v_new, pos, spec):
    return LAYOUT.update(kl, vl, k_new, v_new, {"position_ids": pos}, spec)


def read_layer_cache(kl, vl, spec):
    kk, vv, _ = LAYOUT.read(kl, vl, {}, spec)
    return kk, vv


def make_spec(**kw):
    base = dict(num_layers=2, batch_size=2, num_kv_heads=2, max_len=8, head_dim=4, dtype="float32")
    base.update(kw)
    return KVCacheSpec(**base)


def test_init_shape():
    spec = make_spec()
    cache = init_kv_cache(spec)
    assert cache["k"].shape == (2, 2, 2, 8, 4)
    assert cache["v"].dtype == jnp.float32


def test_update_exact_positions():
    spec = make_spec()
    cache = init_kv_cache(spec)
    k_new = jnp.ones((2, 2, 3, 4)) * 7  # 3 active tokens
    v_new = jnp.ones((2, 2, 3, 4)) * 9
    pos = jnp.array([[0, 1, 2], [2, 3, 4]], dtype=jnp.int32)
    k_l, v_l = update_layer_cache(cache["k"][0], cache["v"][0], k_new, v_new, pos, spec)
    k_np = np.asarray(k_l)
    assert np.all(k_np[0, :, 0:3] == 7) and np.all(k_np[0, :, 3:] == 0)
    assert np.all(k_np[1, :, 2:5] == 7) and np.all(k_np[1, :, :2] == 0)
    assert np.all(np.asarray(v_l)[1, :, 2:5] == 9)


def test_out_of_range_writes_dropped():
    spec = make_spec()
    cache = init_kv_cache(spec)
    k_new = jnp.ones((2, 2, 1, 4))
    pos = jnp.array([[100], [-5]], dtype=jnp.int32)  # both invalid
    k_l, v_l = update_layer_cache(cache["k"][0], cache["v"][0], k_new, k_new, pos, spec)
    assert np.all(np.asarray(k_l) == 0)


def test_overwrite_same_position():
    spec = make_spec()
    cache = init_kv_cache(spec)
    pos = jnp.zeros((2, 1), dtype=jnp.int32)
    a = jnp.ones((2, 2, 1, 4)) * 3
    b = jnp.ones((2, 2, 1, 4)) * 5
    k_l, v_l = update_layer_cache(cache["k"][0], cache["v"][0], a, a, pos, spec)
    k_l, v_l = update_layer_cache(k_l, v_l, b, b, pos, spec)
    assert np.all(np.asarray(k_l)[:, :, 0] == 5)


def test_quantized_cache_round_trip():
    spec = make_spec(quant_dtype="float8_e4m3")
    cache = init_kv_cache(spec)
    assert cache["k"].dtype == jnp.float8_e4m3fn
    k_new = jnp.ones((2, 2, 1, 4)) * 1.5
    pos = jnp.zeros((2, 1), dtype=jnp.int32)
    k_l, v_l = update_layer_cache(cache["k"][0], cache["v"][0], k_new, k_new, pos, spec)
    k_read, _ = read_layer_cache(k_l, v_l, spec)
    assert k_read.dtype == jnp.float32
    assert np.allclose(np.asarray(k_read)[:, :, 0], 1.5)  # 1.5 is exact in e4m3


def test_reset():
    spec = make_spec()
    cache = init_kv_cache(spec)
    cache = {"k": cache["k"] + 1, "v": cache["v"] + 2}
    cache = reset_kv_cache(cache)
    assert np.all(np.asarray(cache["k"]) == 0) and np.all(np.asarray(cache["v"]) == 0)


def test_seq_id_routed_update_and_read():
    """Continuous batching: batch row 0 routed to cache line 1 and vice versa."""
    layout = ContiguousKVLayout(route_by_seq_id=True)
    spec = make_spec()
    cache = init_kv_cache(spec)
    k_new = jnp.stack([jnp.ones((2, 1, 4)) * 3, jnp.ones((2, 1, 4)) * 5])  # (2,2,1,4)
    ci = {
        "position_ids": jnp.zeros((2, 1), jnp.int32),
        "seq_ids": jnp.array([1, 0], jnp.int32),
    }
    k_l, v_l = layout.update(cache["k"][0], cache["v"][0], k_new, k_new, ci, spec)
    k_np = np.asarray(k_l)
    assert np.all(k_np[1, :, 0] == 3) and np.all(k_np[0, :, 0] == 5)
    kk, _, kv_pos = layout.read(k_l, v_l, ci, spec)
    # read gathers back in batch order: row 0 sees line 1 (its own writes)
    assert np.all(np.asarray(kk)[0, :, 0] == 3) and np.all(np.asarray(kk)[1, :, 0] == 5)
    assert kv_pos.shape == (2, 8)


def test_block_layout_scatter_and_gather():
    """The layout takes the whole (L, slots, KV, D) pool and writes and reads
    it at ``layer_idx``; layer 1 of 3 here."""
    layout = BlockKVLayout(block_size=4)
    spec = make_spec()  # dtype fields reused; shape comes from the array
    pool = jnp.zeros((3, 16, 2, 4))  # 3 layers x (4 blocks x 4 slots)
    k_new = jnp.arange(2 * 2 * 3 * 4, dtype=jnp.float32).reshape(2, 2, 3, 4)
    ci = {
        "position_ids": jnp.array([[0, 1, 2], [0, 1, 2]], jnp.int32),
        # row0 -> block 2 (slots 8..), row1 -> block 0 (slots 0..)
        "slot_mapping": jnp.array([[8, 9, 10], [0, 1, 2]], jnp.int32),
        "block_table": jnp.array([[2, -1], [0, -1]], jnp.int32),
        "layer_idx": jnp.int32(1),
    }
    k_l, v_l = layout.update(pool, pool, k_new, k_new, ci, spec)
    k_np = np.asarray(k_l)
    assert k_np.shape == (3, 16, 2, 4)
    assert np.allclose(k_np[1, 8], np.asarray(k_new)[0, :, 0])  # (KV, D) at slot 8
    assert np.allclose(k_np[1, 2], np.asarray(k_new)[1, :, 2])
    kk, _, kv_pos = layout.read(k_l, v_l, ci, spec)
    assert kk.shape == (2, 2, 8, 4)  # 2 table entries x block_size
    assert np.allclose(np.asarray(kk)[0, :, 0], np.asarray(k_new)[0, :, 0])
    # unallocated second block: kv positions pushed out of causal range
    assert np.all(np.asarray(kv_pos)[:, 4:] >= 2**29)
    # the other layers' reads see none of it
    kk0, _, _ = layout.read(k_l, v_l, dict(ci, layer_idx=jnp.int32(0)), spec)
    assert np.all(np.asarray(kk0) == 0)


def test_block_layout_negative_slots_dropped():
    layout = BlockKVLayout(block_size=4)
    spec = make_spec()
    pool = jnp.zeros((3, 8, 2, 4))
    k_new = jnp.ones((1, 2, 2, 4))
    ci = {
        "position_ids": jnp.array([[0, 1]], jnp.int32),
        "slot_mapping": jnp.array([[-1, -1]], jnp.int32),
        "layer_idx": jnp.int32(1),
    }
    k_l, _ = layout.update(pool, pool, k_new, k_new, ci, spec)
    assert np.all(np.asarray(k_l) == 0)


def test_block_layout_update_touches_one_layer_only():
    """``update`` at layer 1 leaves layers 0 and 2 bit-identical, lands the
    live rows at (1, slot), and drops the rows whose slot is negative — also
    with the layer TRACED, as the layer scan hands it over."""
    layout = BlockKVLayout(block_size=4)
    spec = make_spec()
    rng = np.random.default_rng(0)
    k_pool = jnp.asarray(rng.standard_normal((3, 16, 2, 4)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((3, 16, 2, 4)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((2, 2, 3, 4)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((2, 2, 3, 4)), jnp.float32)
    ci = {
        "position_ids": jnp.array([[0, 1, 2], [0, 1, 2]], jnp.int32),
        "slot_mapping": jnp.array([[8, 9, -1], [-1, 1, 2]], jnp.int32),
    }

    def update(layer):
        return layout.update(
            k_pool, v_pool, k_new, v_new, dict(ci, layer_idx=layer), spec
        )

    for got_k, got_v in (update(jnp.int32(1)), jax.jit(update)(jnp.int32(1))):
        want_k, want_v = np.array(k_pool), np.array(v_pool)
        for b, t, slot in ((0, 0, 8), (0, 1, 9), (1, 1, 1), (1, 2, 2)):
            want_k[1, slot] = np.asarray(k_new)[b, :, t]
            want_v[1, slot] = np.asarray(v_new)[b, :, t]
        np.testing.assert_array_equal(np.asarray(got_k), want_k)
        np.testing.assert_array_equal(np.asarray(got_v), want_v)
        for layer in (0, 2):
            np.testing.assert_array_equal(
                np.asarray(got_k)[layer], np.asarray(k_pool)[layer]
            )
