"""Mosaic-compiled parity for the round-4 kernels on real TPU hardware:
the fused gate/up/down MLP kernel and the fused-QKV matmul kernel
(ops/kernels/fused_proj.py), including the stacked scalar-prefetch variants
the layer scan uses.

Run with:  python -m pytest tests/tpu/test_mosaic_kernels_r4.py -q
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import nxdi_tpu.ops.kernels.fused_proj as fk

pytestmark = pytest.mark.usefixtures("tpu")


def _rand(shape, seed=0, scale=0.05, dtype=jnp.bfloat16):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape) * scale, dtype
    )


def _ref_mlp(x, g, u, d):
    xf = x.astype(jnp.float32)
    return (
        jax.nn.silu(xf @ g.astype(jnp.float32)) * (xf @ u.astype(jnp.float32))
    ) @ d.astype(jnp.float32)


@pytest.mark.parametrize("m", [32, 1024])
def test_mosaic_fused_mlp_1b_shape(m):
    H, I = 2048, 8192  # Llama-3.2-1B
    x = _rand((m, H), 1)
    g = _rand((H, I), 2)
    u = _rand((H, I), 3)
    d = _rand((I, H), 4)
    got = np.asarray(fk.fused_mlp(x, g, u, d)).astype(np.float32)
    want = np.asarray(_ref_mlp(x, g, u, d))
    denom = max(1e-3, float(np.abs(want).max()))
    assert np.abs(got - want).max() / denom < 0.05


def test_mosaic_fused_mlp_stacked_layers():
    L, M, H, I = 4, 32, 2048, 8192
    x = _rand((M, H), 1)
    gs = _rand((L, H, I), 2)
    us = _rand((L, H, I), 3)
    ds = _rand((L, I, H), 4)
    for li in (0, 3):
        got = np.asarray(
            fk.fused_mlp_stacked(x, gs, us, ds, jnp.array([li], jnp.int32))
        ).astype(np.float32)
        want = np.asarray(_ref_mlp(x, gs[li], us[li], ds[li]))
        denom = max(1e-3, float(np.abs(want).max()))
        assert np.abs(got - want).max() / denom < 0.05


@pytest.mark.parametrize("bias", [False, True])
def test_mosaic_qkv_matmul(bias):
    M, H, T = 32, 2048, 3072  # 1B fused q|k|v width
    x = _rand((M, H), 5)
    w = _rand((H, T), 6)
    b = _rand((T,), 7) if bias else None
    got = np.asarray(fk.qkv_matmul(x, w, b)).astype(np.float32)
    want = np.asarray(x.astype(jnp.float32) @ w.astype(jnp.float32))
    if bias:
        want = want + np.asarray(b, np.float32)
    denom = max(1e-3, float(np.abs(want).max()))
    assert np.abs(got - want).max() / denom < 0.05


def test_mosaic_qkv_matmul_stacked():
    L, M, H, T = 3, 32, 2048, 3072
    x = _rand((M, H), 8)
    ws = _rand((L, H, T), 9)
    bs = _rand((L, T), 10)
    for li in (0, 2):
        got = np.asarray(
            fk.qkv_matmul_stacked(x, ws, jnp.array([li], jnp.int32), bs)
        ).astype(np.float32)
        want = np.asarray(
            x.astype(jnp.float32) @ ws[li].astype(jnp.float32)
        ) + np.asarray(bs[li], np.float32)
        denom = max(1e-3, float(np.abs(want).max()))
        assert np.abs(got - want).max() / denom < 0.05
