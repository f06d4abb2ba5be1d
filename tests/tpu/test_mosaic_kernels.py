"""Mosaic-compiled kernel parity on real TPU hardware.

The CPU suite exercises the Pallas kernels in interpreter mode only; this
file compiles them with Mosaic and checks numerics against the XLA path on
the hub's real head dims (64 / 96 / 128 — llama-1B/3B, phi, llama-8B).

Run with:  NXDI_TPU_HW_TESTS=1 python -m pytest tests/tpu/ -q
Skipped automatically when no TPU is attached (the default CPU-forced suite
never reaches the Mosaic path, reference analog: NKI kernel unit tests run
on-device, test/unit/modules/kernels).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from nxdi_tpu.ops.attention import attention_with_positions
from nxdi_tpu.ops.kernels import flash_attention_decode, flash_attention_prefill
from nxdi_tpu.ops.kernels.flash_attention import (
    decode_kernel_supported,
    prefill_kernel_supported,
)

pytestmark = pytest.mark.usefixtures("tpu")


def _rand(shape, seed=0, dtype=jnp.bfloat16):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape) * 0.5, dtype
    )


@pytest.mark.parametrize("D", [64, 96, 128])
@pytest.mark.parametrize("window", [None, 48])
def test_mosaic_prefill_head_dims(D, window):
    B, H, KV, S = 2, 8, 4, 256
    q, k, v = _rand((B, H, S, D)), _rand((B, KV, S, D), 1), _rand((B, KV, S, D), 2)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    assert prefill_kernel_supported(q.shape, k.shape)
    expected = attention_with_positions(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        pos, pos, sliding_window=window,
    )
    actual = flash_attention_prefill(q, k, v, pos, pos, sliding_window=window)
    np.testing.assert_allclose(
        np.asarray(actual, np.float32), np.asarray(expected), atol=2e-2
    )


@pytest.mark.parametrize("D", [64, 96, 128])
def test_mosaic_decode_head_dims(D):
    B, H, KV, W = 2, 8, 2, 512
    q = _rand((B, H, 1, D))
    k, v = _rand((B, KV, W, D), 1), _rand((B, KV, W, D), 2)
    q_pos = jnp.array([[300], [17]], jnp.int32)
    kv_pos = jnp.tile(jnp.arange(W, dtype=jnp.int32), (B, 1))
    assert decode_kernel_supported(q.shape, k.shape)
    expected = attention_with_positions(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        q_pos, kv_pos,
    )
    actual = flash_attention_decode(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(
        np.asarray(actual, np.float32), np.asarray(expected), atol=2e-2
    )
