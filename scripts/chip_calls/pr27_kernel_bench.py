#!/usr/bin/env python3
"""PR 27: the paged decode kernel alone, at the `qwen25-3b` cell's shapes, on the chip.

    chiprun --chips 1 -- python3 scripts/chip_calls/pr27_kernel_bench.py [--pages 4,8,16]

A (36, 57344, 2, 128) bf16 K and V pool (1.97 GiB), 64 rows of 3-7 live blocks
(60 % of the pool, as the saturated cell holds), a 32-entry table. One "step" is a
`lax.scan` over the 36 layers that calls the kernel at each layer's index with the
pool closed over, as the token-generation program does. Prints, per variant, the
worst difference to the XLA gather reference and the milliseconds per step
(median of 20, each ended by `block_until_ready`). Variants: the repo's kernel at
several `PAGED_DECODE_PAGES_PER_STEP`, `auto`: one (128, KV, D) BlockSpec per table
entry (what Pallas pipelines by itself: one block in flight, moved as 512 B tiles), and
`auto3d`: the same through the pool's (L, slots * KV, D) view (4 KiB tiles).
"""
import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nxdi_tpu.ops.kernels import flash_attention as fa

L, BLOCKS, BS, KV, D, B, H, NB = 36, 448, 128, 2, 128, 64, 16, 32
G = H // KV


def auto_kernel(li_ref, bt_ref, qp_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale):
    bi, b = pl.program_id(1), pl.program_id(0)
    q_pos, bt = qp_ref[b], bt_ref[b, bi]

    @pl.when(bi == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, fa.NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when((bt >= 0) & (bi * BS <= q_pos))
    def _():
        kv_pos = bi * BS + jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)
        mask = jnp.broadcast_to(kv_pos <= q_pos, (G, BS))
        for kv in range(KV):
            s = jax.lax.dot_general(
                q_ref[0, kv], k_ref[:, kv, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            fa._online_softmax_step(
                s, mask, m_ref, l_ref, acc_ref, v_ref[:, kv, :], sl=slice(kv * G, (kv + 1) * G)
            )

    @pl.when(bi == NB - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[0] = (acc_ref[:] / l[:, None]).reshape(KV, G, D).astype(o_ref.dtype)


def auto_call(q, k, v, bt, qp, li, *, block_size):
    def cache_index(b, bi, li_ref, bt_ref, qp_ref):
        return li_ref[0], jnp.maximum(bt_ref[b, bi], 0), 0, 0

    out = pl.pallas_call(
        functools.partial(auto_kernel, scale=D ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, NB),
            in_specs=[
                pl.BlockSpec((1, KV, G, D), lambda b, bi, *_: (b, 0, 0, 0)),
                pl.BlockSpec((None, BS, KV, D), cache_index),
                pl.BlockSpec((None, BS, KV, D), cache_index),
            ],
            out_specs=pl.BlockSpec((1, KV, G, D), lambda b, bi, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        name="paged_attention_decode_auto",
    )(jnp.asarray(li, jnp.int32).reshape(1), bt, qp[:, 0], q.reshape(B, KV, G, D), k, v)
    return out.reshape(B, H, 1, D)


def auto3d_kernel(li_ref, bt_ref, qp_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale):
    bi, b = pl.program_id(1), pl.program_id(0)
    q_pos, bt = qp_ref[b], bt_ref[b, bi]

    @pl.when(bi == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, fa.NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when((bt >= 0) & (bi * BS <= q_pos))
    def _():
        col = jax.lax.broadcasted_iota(jnp.int32, (H, BS * KV), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (H, BS * KV), 0) // G
        mask = ((col % KV) == head) & (bi * BS + col // KV <= q_pos)
        s = jax.lax.dot_general(
            q_ref[0].reshape(H, D), k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        fa._online_softmax_step(s, mask, m_ref, l_ref, acc_ref, v_ref[...])

    @pl.when(bi == NB - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[0] = (acc_ref[:] / l[:, None]).reshape(KV, G, D).astype(o_ref.dtype)


def auto3d_call(q, k, v, bt, qp, li, *, block_size):
    def cache_index(b, bi, li_ref, bt_ref, qp_ref):
        return li_ref[0], jnp.maximum(bt_ref[b, bi], 0), 0

    out = pl.pallas_call(
        functools.partial(auto3d_kernel, scale=D ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, NB),
            in_specs=[
                pl.BlockSpec((1, KV, G, D), lambda b, bi, *_: (b, 0, 0, 0)),
                pl.BlockSpec((None, BS * KV, D), cache_index),
                pl.BlockSpec((None, BS * KV, D), cache_index),
            ],
            out_specs=pl.BlockSpec((1, KV, G, D), lambda b, bi, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        name="paged_attention_decode_auto3d",
    )(
        jnp.asarray(li, jnp.int32).reshape(1), bt, qp[:, 0], q.reshape(B, KV, G, D),
        k.reshape(L, -1, D), v.reshape(L, -1, D),
    )
    return out.reshape(B, H, 1, D)


def reference(q, k, v, bt, qp, layer):
    from nxdi_tpu.ops.attention import attention_with_positions

    offs = jnp.arange(BS, dtype=jnp.int32)
    slots = (jnp.maximum(bt, 0)[:, :, None] * BS + offs[None, None, :]).reshape(B, -1)
    kk = jnp.swapaxes(k[layer][slots], 1, 2)
    vv = jnp.swapaxes(v[layer][slots], 1, 2)
    pos = jnp.broadcast_to(jnp.arange(NB * BS, dtype=jnp.int32)[None], (B, NB * BS))
    pos = jnp.where(jnp.repeat(bt >= 0, BS, axis=1), pos, jnp.int32(2**30))
    return attention_with_positions(q, kk, vv, qp, pos)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", default="4,8,16")
    ap.add_argument("--out", default="chiprun_out/pr27/kernel_bench.json")
    args = ap.parse_args()
    dev = jax.devices()[0]
    assert dev.platform == "tpu", dev
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    k = (jax.random.normal(key, (L, BLOCKS * BS, KV, D), jnp.float32) * 0.5).astype(jnp.bfloat16)
    v = (jax.random.normal(jax.random.fold_in(key, 1), (L, BLOCKS * BS, KV, D), jnp.float32) * 0.5).astype(jnp.bfloat16)
    q = (jax.random.normal(jax.random.fold_in(key, 2), (B, H, 1, D), jnp.float32)).astype(jnp.bfloat16)
    lens = rng.integers(3 * BS - 100, 7 * BS, size=B)  # positions: 3..7 live blocks
    perm = rng.permutation(BLOCKS)
    bt = np.full((B, NB), -1, np.int32)
    at = 0
    for r in range(B):
        n = lens[r] // BS + 1
        bt[r, :n] = perm[at:at + n]
        at += n
    print(f"live blocks {at} of {BLOCKS} ({100 * at / BLOCKS:.1f} %)", flush=True)
    bt = jnp.asarray(bt)
    qp = jnp.asarray(lens[:, None].astype(np.int32))
    want = np.asarray(jax.jit(reference, static_argnums=5)(q, k, v, bt, qp, 5).astype(jnp.float32))

    def step_of(call):
        @jax.jit
        def step(q, k, v, bt, qp):
            def body(acc, li):
                out = call(q, k, v, bt, qp, li, block_size=BS)
                return acc + out.astype(jnp.float32), None

            acc, _ = jax.lax.scan(body, jnp.zeros((B, H, 1, D), jnp.float32), jnp.arange(L, dtype=jnp.int32))
            return acc

        return step

    results = {}
    variants = [("auto", auto_call, None), ("auto3d", auto3d_call, None)] + [(f"pages{p}", fa.paged_attention_decode, int(p)) for p in args.pages.split(",")]
    for name, call, pages in variants:
        if pages is not None:
            fa.PAGED_DECODE_PAGES_PER_STEP = pages
        got = np.asarray(jax.jit(lambda *a: call(*a, 5, block_size=BS))(q, k, v, bt, qp).astype(jnp.float32))
        err = float(np.max(np.abs(got - want)))
        step = step_of(call)
        jax.block_until_ready(step(q, k, v, bt, qp))
        times = []
        for _ in range(20):
            t0 = time.perf_counter()
            jax.block_until_ready(step(q, k, v, bt, qp))
            times.append((time.perf_counter() - t0) * 1e3)
        results[name] = {"max_abs_err_vs_gather": err, "ms_per_36_launches": statistics.median(times), "min_ms": min(times)}
        print(name, json.dumps(results[name]), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    json.dump({"device": dev.device_kind, "results": results}, open(args.out, "w"), indent=1)


if __name__ == "__main__":
    main()
