"""Every Pallas kernel carries the name of the public function that launches
it. In a profiler trace the kernel is the HLO instruction ``<name>.<n>`` (a
``custom-call``), so a reducer finds ``paged_attention_decode`` after any
refactor of the kernel's body; without ``name=`` all twelve were ``name``
(their bodies are ``functools.partial`` objects, which have no ``__name__``).

Traced at small shapes on the CPU (interpret mode); nothing runs."""

import importlib

import jax
import jax.numpy as jnp
import pytest

from nxdi_tpu.ops.kernels import flash_attention as fa
from nxdi_tpu.ops.kernels import fused_proj as fp
from nxdi_tpu.ops.kernels import kv_commit

# the package re-exports the function under the module's own name
rpa = importlib.import_module("nxdi_tpu.ops.kernels.ragged_paged_attention")

F32, I32 = jnp.float32, jnp.int32
B, H, KV, D, S = 2, 4, 2, 128, 128  # rows, q heads, kv heads, head dim, window
BLOCK, NB = 128, 4  # paged pool: block size, blocks
L, HID, INTER = 2, 128, 256  # stacked layers, hidden, intermediate


def _s(*shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype)


Q1 = _s(B, H, 1, D)  # decode queries
QS = _s(1, H, S, D)  # prefill queries
POOL = _s(L, NB * BLOCK, KV, D)  # the whole layer-stacked paged pool
CACHE = _s(B, KV, S, D)
ROW = _s(B, KV, 1, D)
X = _s(8, HID)

#: name -> (callable over positional arrays, their shapes)
KERNELS = {
    "flash_attention_prefill": (
        fa.flash_attention_prefill,
        (QS, _s(1, KV, S, D), _s(1, KV, S, D), _s(1, S, dtype=I32), _s(1, S, dtype=I32)),
    ),
    "flash_attention_decode": (
        fa.flash_attention_decode,
        (Q1, CACHE, CACHE, _s(B, 1, dtype=I32), _s(B, S, dtype=I32)),
    ),
    "flash_attention_decode_fused": (
        fa.flash_attention_decode_fused,
        (Q1, CACHE, CACHE, ROW, ROW, _s(B, 1, dtype=I32), _s(B, S, dtype=I32)),
    ),
    "flash_attention_decode_fused_stacked": (
        fa.flash_attention_decode_fused_stacked,
        (Q1, _s(L, B, KV, S, D), _s(L, B, KV, S, D), ROW, ROW,
         _s(B, 1, dtype=I32), _s(1, dtype=I32)),
    ),
    "paged_attention_decode": (
        lambda q, k, v, bt, pos, li: fa.paged_attention_decode(
            q, k, v, bt, pos, li, block_size=BLOCK),
        (Q1, POOL, POOL, _s(B, NB, dtype=I32), _s(B, 1, dtype=I32), _s(1, dtype=I32)),
    ),
    "paged_attention_prefill": (
        lambda q, k, v, bt, pos, li: fa.paged_attention_prefill(
            q, k, v, bt, pos, li, block_size=BLOCK),
        (QS, POOL, POOL, _s(1, NB, dtype=I32), _s(1, S, dtype=I32), _s(1, dtype=I32)),
    ),
    "ragged_paged_attention": (
        lambda q, k, v, bt, rid, pos, li: rpa.ragged_paged_attention(
            q, k, v, bt, rid, pos, li, block_size=BLOCK),
        (QS, POOL, POOL, _s(B, NB, dtype=I32), _s(S, dtype=I32), _s(S, dtype=I32),
         _s(1, dtype=I32)),
    ),
    "kv_commit_rows": (
        kv_commit.kv_commit_rows,
        (_s(L, B, KV, S, D), _s(L, B, KV, S, D), _s(L, B, KV, 1, D), _s(L, B, KV, 1, D),
         _s(B, 1, dtype=I32)),
    ),
    "fused_mlp": (fp.fused_mlp, (X, _s(HID, INTER), _s(HID, INTER), _s(INTER, HID))),
    "fused_mlp_stacked": (
        fp.fused_mlp_stacked,
        (X, _s(L, HID, INTER), _s(L, HID, INTER), _s(L, INTER, HID), _s(1, dtype=I32)),
    ),
    "qkv_matmul": (fp.qkv_matmul, (X, _s(HID, 3 * HID))),
    "qkv_matmul_stacked": (
        fp.qkv_matmul_stacked, (X, _s(L, HID, 3 * HID), _s(1, dtype=I32)),
    ),
}


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_every_public_kernel_is_listed():
    """The twelve ``pl.pallas_call`` sites under ``ops/kernels/``, and no
    thirteenth that this file has not been told of."""
    import inspect

    sites = sum(
        inspect.getsource(m).count("pl.pallas_call(") for m in (fa, fp, kv_commit, rpa)
    )
    assert sites == len(KERNELS) == 12


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_call_carries_the_name_of_its_entry_point(name):
    fn, shapes = KERNELS[name]
    calls = list(_pallas_calls(jax.make_jaxpr(fn)(*shapes).jaxpr))
    assert len(calls) == 1
    assert calls[0].params["name"] == name
