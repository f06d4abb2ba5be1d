"""Linear attention with a per-head exponential decay (Lightning Attention,
arXiv:2401.04658), float32 state ``S_t = g S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``.

Two forms of one recurrence:

- :func:`chunked_prefill`: a fresh prompt in chunks of ``chunk`` tokens. Inside
  a chunk ``((Q K^T) * D) V`` with ``D_ij = g^(i-j)``; across chunks the state
  before chunk ``c`` by a scan over the chunks' own summaries
  ``(K * g^(C-1-i))^T V``. Every exponent is ``<= 0``: nothing overflows, a
  fast head's far past underflows to 0 as it should. The state it hands back is
  the one AT each row's last real token (bucket padding behind it never lands).
- :func:`decode_step`: one token a row on a store of states a SLOT,
  ``(L, slots, H, D, D)``, read and written at ``(layer, slot)`` in place: the
  Pallas kernel aliases the store input to output and moves a row's heads once
  in, once out; the XLA form is a gather, the update and a scatter.

Plain XLA but for the decode kernel; float32 throughout (a 16k prefill is
~0.1 TFLOP a layer at six passes: milliseconds).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nxdi_tpu.ops.kernels import mode

DEFAULT_CHUNK = 256
#: heads one grid step of the decode kernel moves: 8 x (128, 128) float32 is
#: 512 KiB in and out, ~1.3 us of HBM time against ~0.35 us a step
DECODE_HEADS_PER_STEP = 8


def decay_rates(num_heads: int, layer: int, total_layers: int) -> np.ndarray:
    """``-log g`` a head: Lightning Attention's slopes ``2^(-8 (h+1) / heads)``
    times MiniMax-01's layer factor ``1 - layer / (total - 1) + 1e-5``."""
    slope = 2.0 ** (-8.0 * (np.arange(num_heads) + 1) / num_heads)
    return (slope * (1.0 - layer / max(total_layers - 1, 1) + 1e-5)).astype(np.float32)


def chunked_prefill(q, k, v, rates, last_index, chunk: int = DEFAULT_CHUNK):
    """``q, k, v`` (B, S, H, D) from position 0 with an empty state (``q``
    already scaled); ``rates`` (H,) ``-log g``; ``last_index`` (B,) each row's
    last real token. Returns ``(o (B, S, H, D) float32, state (B, H, D, D)
    float32 at last_index)``."""
    B, S, H, D = q.shape
    C = min(chunk, S)
    if S % C:
        raise ValueError(f"a prompt of {S} tokens is no multiple of the chunk {C}")
    N = S // C
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    r = jnp.asarray(rates, f32)
    qf, kf, vf = (x.astype(f32).reshape(B, N, C, H, D) for x in (q, k, v))
    i = jnp.arange(C, dtype=f32)
    with jax.named_scope("lin.chunk"):
        diff = i[:, None] - i[None, :]
        decay = jnp.where(diff >= 0, jnp.exp(-r[:, None, None] * jnp.maximum(diff, 0.0)), 0.0)
        s = jnp.einsum("bnihd,bnjhd->bnhij", qf, kf, precision=hp) * decay
        o = jnp.einsum("bnhij,bnjhd->bnihd", s, vf, precision=hp)
        if N > 1:
            k_end = kf * jnp.exp(-r[None, :, None] * (C - 1 - i)[:, None, None])
            kv = jnp.einsum("bnjhd,bnjhe->nbhde", k_end, vf, precision=hp)
            g_chunk = jnp.exp(-r * C)[:, None, None]
            _, before = jax.lax.scan(  # the state BEFORE each chunk
                lambda st, kv_c: (g_chunk * st + kv_c, st), jnp.zeros((B, H, D, D), f32), kv
            )
            q_in = qf * jnp.exp(-r[None, :, None] * (i + 1.0)[:, None, None])
            o = o + jnp.einsum("bnihd,nbhde->bnihe", q_in, before, precision=hp)
    with jax.named_scope("lin.state"):
        t = jnp.arange(S, dtype=jnp.int32)[None, :]
        age = (last_index.astype(jnp.int32)[:, None] - t).astype(f32)  # (B, S)
        w = jnp.where(
            (age >= 0)[..., None], jnp.exp(-r[None, None, :] * jnp.maximum(age, 0.0)[..., None]), 0.0
        )
        state = jnp.einsum(
            "bshd,bshe->bhde", k.astype(f32) * w[..., None], v.astype(f32), precision=hp
        )
    return o.reshape(B, S, H, D), state


def recurrence(q, k, v, rates, state=None):
    """The recurrence itself, one token a step (tests; ``q`` already scaled):
    ``(o (B, S, H, D), state (B, H, D, D))``."""
    B, S, H, D = q.shape
    f32 = jnp.float32
    g = jnp.exp(-jnp.asarray(rates, f32))[None, :, None, None]
    state = jnp.zeros((B, H, D, D), f32) if state is None else state

    def step(st, qkv):
        q_t, k_t, v_t = (x.astype(f32) for x in qkv)
        st = g * st + k_t[..., :, None] * v_t[..., None, :]
        return st, jnp.einsum("bhd,bhde->bhe", q_t, st, precision=jax.lax.Precision.HIGHEST)

    state, o = jax.lax.scan(step, state, tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v)))
    return jnp.swapaxes(o, 0, 1), state


def write_states(store, layer, slot_ids, states):
    """A prefill's states (B, H, D, D) into the store at ``(layer, slot)``."""
    for b in range(states.shape[0]):
        store = jax.lax.dynamic_update_slice(
            store, states[b][None, None].astype(store.dtype), (layer, slot_ids[b], 0, 0, 0)
        )
    return store


def _decode_kernel(li_ref, sid_ref, g_ref, q_ref, k_ref, v_ref, s_ref, o_ref, s_out_ref):
    del li_ref, sid_ref  # consumed by the index maps
    g = g_ref[...]  # (hb, 1)
    k = k_ref[0].astype(jnp.float32)  # (hb, D)
    v = v_ref[0].astype(jnp.float32)
    q = q_ref[0].astype(jnp.float32)
    state = g[:, :, None] * s_ref[...] + k[:, :, None] * v[:, None, :]
    s_out_ref[...] = state
    o_ref[0] = jnp.sum(q[:, :, None] * state, axis=1)


def decode_kernel_supported(q_shape) -> bool:
    H, D = q_shape[-2:]
    if mode.interpret():
        return True
    return D % 128 == 0 and H % min(DECODE_HEADS_PER_STEP, H) == 0


def _decode_step_kernel(store, layer, slot_ids, q, k, v, rates):
    B, H, D = q.shape
    hb = min(DECODE_HEADS_PER_STEP, H)
    g = jnp.exp(-jnp.asarray(rates, jnp.float32)).reshape(H, 1)

    def row(b, j, li, sid):
        return (b, j, 0)

    def state_at(b, j, li, sid):
        return (li[0], sid[b], j, 0, 0)

    o, store = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb),
            in_specs=[
                pl.BlockSpec((hb, 1), lambda b, j, li, sid: (j, 0)),
                pl.BlockSpec((1, hb, D), row),
                pl.BlockSpec((1, hb, D), row),
                pl.BlockSpec((1, hb, D), row),
                pl.BlockSpec((None, None, hb, D, D), state_at),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, D), row),
                pl.BlockSpec((None, None, hb, D, D), state_at),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operands count the two prefetched scalars: the store is the seventh
        input_output_aliases={6: 1},
        name="lightning_decode_step",
        interpret=mode.interpret(),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), slot_ids.astype(jnp.int32),
        g, q, k, v, store,
    )
    return o, store


def decode_step(store, layer, slot_ids, q, k, v, rates, use_kernel=None):
    """One token a row: ``store`` (L, slots, H, D, D) float32 read and written
    at ``(layer, slot_ids[b])``; ``q`` (scaled), ``k``, ``v`` (B, H, D). No two
    rows may name one slot (the caller sends a batch's padding rows to a spare
    slot). Returns ``(o (B, H, D) float32, store)``."""
    if use_kernel is None:
        use_kernel = decode_kernel_supported(q.shape)
    with jax.named_scope("lin.step"):
        if use_kernel:
            return _decode_step_kernel(store, layer, slot_ids, q, k, v, rates)
        f32 = jnp.float32
        g = jnp.exp(-jnp.asarray(rates, f32))[None, :, None, None]
        old = store[layer, slot_ids]
        new = g * old + k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :]
        o = jnp.einsum("bhd,bhde->bhe", q.astype(f32), new, precision=jax.lax.Precision.HIGHEST)
        return o, store.at[layer, slot_ids].set(new)
