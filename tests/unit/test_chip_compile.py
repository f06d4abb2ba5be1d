"""AOT compiles of the main path's Pallas kernels for a DESCRIBED v5e chip.

No chip is attached here: the TPU compiler compiles for ``v5e:2x2`` as it is
described (``jax.experimental.topologies``), which is what refuses a kernel
the interpreter accepts — a slice off the tiling, too much fast memory.
Llama-3.2-1B (D=64, 32/8 heads, H=2048, I=8192) and Llama-3.1-8B (D=128,
32/8 heads, H=4096, I=14336) shapes, one launch each, ~2 s a compile.

A compile that passes is a compile, never a run: numerics of the compiled
kernels live in tests/tpu/ and run on the chip.

The topology is described inside a fixture (never at import: one process at
a time may load the TPU's library, and every xdist worker imports this file),
kernels are steered off the interpreter by monkeypatch, and the persistent
compilation cache is off around the compiles (an entry written for a
described chip cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from nxdi_tpu.ops.kernels import flash_attention as fa
from nxdi_tpu.ops.kernels import fused_proj as fp
from nxdi_tpu.ops.kernels import kv_commit, mode
from nxdi_tpu.ops.kernels.ragged_paged_attention import (
    ragged_paged_attention,
    ragged_paged_kernel_supported,
)

BF16 = jnp.bfloat16
I32 = jnp.int32

# (head_dim, q heads, kv heads, hidden, intermediate)
WIDTHS = {
    "1b": dict(D=64, H=32, KV=8, hidden=2048, inter=8192),
    "8b": dict(D=128, H=32, KV=8, hidden=4096, inter=14336),
}
BLOCK = 128  # pa_block_size of the serving stack
SEQ = 2048  # decode window
PROMPT = 1024  # prompt bucket
SLOTS = 8  # decode batch rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_compile_cache):
    """Kernels lower through Mosaic, not the interpreter."""
    monkeypatch.setattr(mode, "interpret", lambda: False)


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; returns the optimized HLO."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


WIDTH_IDS = sorted(WIDTHS)


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_flash_prefill(width, one_chip, mosaic):
    w = WIDTHS[width]
    q = ((1, w["H"], PROMPT, w["D"]), BF16)
    kv = ((1, w["KV"], PROMPT, w["D"]), BF16)
    pos = ((1, PROMPT), I32)
    assert fa.prefill_kernel_supported(q[0], kv[0])
    _compile(fa.flash_attention_prefill, one_chip, q, kv, kv, pos, pos)


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_flash_decode(width, one_chip, mosaic):
    w = WIDTHS[width]
    q = ((SLOTS, w["H"], 1, w["D"]), BF16)
    kv = ((SLOTS, w["KV"], SEQ, w["D"]), BF16)
    assert fa.decode_kernel_supported(q[0], kv[0])
    _compile(
        fa.flash_attention_decode, one_chip,
        q, kv, kv, ((SLOTS, 1), I32), ((SLOTS, SEQ), I32),
    )


def _pool(w):
    n_blocks = SLOTS * (SEQ // BLOCK) + SLOTS
    return ((n_blocks * BLOCK, w["KV"], w["D"]), BF16)


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_paged_prefill(width, one_chip, mosaic):
    w = WIDTHS[width]
    q = ((1, w["H"], PROMPT, w["D"]), BF16)
    pool = _pool(w)
    assert fa.paged_prefill_kernel_supported(q[0], pool[0], BLOCK)
    _compile(
        lambda q, k, v, bt, pos: fa.paged_attention_prefill(
            q, k, v, bt, pos, block_size=BLOCK
        ),
        one_chip, q, pool, pool, ((1, SEQ // BLOCK), I32), ((1, PROMPT), I32),
    )


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_paged_decode(width, one_chip, mosaic):
    w = WIDTHS[width]
    q = ((SLOTS, w["H"], 1, w["D"]), BF16)
    pool = _pool(w)
    assert fa.paged_decode_kernel_supported(q[0], pool[0], BLOCK)
    _compile(
        lambda q, k, v, bt, pos: fa.paged_attention_decode(
            q, k, v, bt, pos, block_size=BLOCK
        ),
        one_chip, q, pool, pool, ((SLOTS, SEQ // BLOCK), I32), ((SLOTS, 1), I32),
    )


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_ragged_paged(width, one_chip, mosaic):
    """Mixed dispatch's packed stream: one 1024-token prompt + 8 decode rows
    padded to the 1040-token rung."""
    w = WIDTHS[width]
    T = PROMPT + 16
    q = ((1, w["H"], T, w["D"]), BF16)
    pool = _pool(w)
    assert ragged_paged_kernel_supported(q[0], pool[0], BLOCK)
    _compile(
        lambda q, k, v, bt, rid, pos: ragged_paged_attention(
            q, k, v, bt, rid, pos, block_size=BLOCK
        ),
        one_chip, q, pool, pool,
        ((SLOTS + 1, SEQ // BLOCK), I32), ((T,), I32), ((T,), I32),
    )


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_kv_commit_rows(width, one_chip, mosaic):
    w = WIDTHS[width]
    L = 16 if width == "1b" else 32
    cache = ((L, SLOTS, w["KV"], SEQ, w["D"]), BF16)
    rows = ((L, SLOTS, w["KV"], 1, w["D"]), BF16)
    assert kv_commit.commit_rows_supported(cache[0], cache[0], rows[0], rows[0])
    _compile(
        kv_commit.kv_commit_rows, one_chip,
        cache, cache, rows, rows, ((SLOTS, 1), I32),
    )


@pytest.mark.parametrize("m", [SLOTS, PROMPT], ids=["decode", "prefill"])
@pytest.mark.parametrize("width", WIDTH_IDS)
def test_fused_mlp(width, m, one_chip, mosaic):
    w = WIDTHS[width]
    hid, inter = w["hidden"], w["inter"]
    assert fp.fused_mlp_supported(m, hid, inter, "silu")
    x = ((m, hid), BF16)
    up = ((hid, inter), BF16)
    down = ((inter, hid), BF16)
    _compile(fp.fused_mlp, one_chip, x, up, up, down)
    L = 2  # the stacked variant indexes a layer of the (L, ...) scan stack
    _compile(
        fp.fused_mlp_stacked, one_chip,
        x, ((L,) + up[0], BF16), ((L,) + up[0], BF16), ((L,) + down[0], BF16),
        ((1,), I32),
    )


@pytest.mark.parametrize("m", [SLOTS, PROMPT], ids=["decode", "prefill"])
@pytest.mark.parametrize("width", WIDTH_IDS)
def test_fused_qkv(width, m, one_chip, mosaic):
    w = WIDTHS[width]
    hid = w["hidden"]
    t = (w["H"] + 2 * w["KV"]) * w["D"]
    assert fp.qkv_matmul_supported(m, hid, t)
    x = ((m, hid), BF16)
    _compile(fp.qkv_matmul, one_chip, x, ((hid, t), BF16))
    _compile(
        fp.qkv_matmul_stacked, one_chip, x, ((2, hid, t), BF16), ((1,), I32)
    )


def _kernel_payloads(lowered_text: str):
    return [ln for ln in lowered_text.splitlines() if "tpu_custom_call" in ln]


def test_kernel_bytes_do_not_depend_on_the_call_path(one_chip, mosaic):
    """A Mosaic kernel rides in its program as serialized bytes that keep
    their MLIR locations, and JAX's default puts the Python traceback of the
    ``pallas_call`` there: the same program lowered from ``compile()`` and
    from ``load()`` then hashes to two persistent-cache keys (seen on the
    v5e for the TKG program, whose commit kernel sits few frames deep).
    Under the setting ``enable_persistent_cache`` makes, the bytes are the
    same from every caller."""
    w = WIDTHS["1b"]
    cache = jax.ShapeDtypeStruct((16, SLOTS, w["KV"], SEQ, w["D"]), BF16, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((16, SLOTS, w["KV"], 1, w["D"]), BF16, sharding=one_chip)
    slots = jax.ShapeDtypeStruct((SLOTS, 1), I32, sharding=one_chip)

    def lower():
        # a fresh jit each time: nothing is served from a lowering cache
        fn = jax.jit(lambda *a: kv_commit.kv_commit_rows(*a))
        return _kernel_payloads(fn.lower(cache, cache, rows, rows, slots).as_text())

    def from_another_caller():
        return lower()

    was = jax.config.jax_include_full_tracebacks_in_locations
    try:
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
        assert lower() != from_another_caller()  # the default: path-dependent
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
        assert lower() == from_another_caller()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
