"""Which blocks of the pool a query reads (InfLLM-V2, arXiv:2509.24663, as
MiniCPM4 ships it): scores over an INDEX of compressed keys, pooled to the
blocks of the paged pool, the best ``topk`` kept with the forced ones.

A KV head with its group of query heads, the query at position ``t``:
``Kc_j = mean(k[stride j : stride j + kernel])`` for every window wholly at or
before ``t``; ``p_hj = softmax_j(q_h Kc_j scale)``; ``P_j`` its sum over the
group; a block's score the largest ``P_j`` over the windows that overlap it;
selected: the first ``init_blocks`` blocks, the blocks that hold any of the last
``window_size`` tokens, the best-scored others up to ``topk`` in all. A query at
a position under ``dense_len`` reads every block at or before its own.

- :func:`compress_keys`: a prompt's index rows by one strided mean.
- :func:`decode_tables`: a decode step's selection as a COMPACT block table a
  (row, KV head): the selected blocks' ids in logical order, holes behind them,
  and the position the row's query has in that compact order. Every selected
  block but the last is whole and wholly visible and NoPE keys carry no
  position, so the paged decode kernel reads the compact table as it reads any
  other: ``topk`` entries a head where the row's own table has hundreds.
- :func:`prefill_mask`: a fresh prompt's selection a query token as a block mask
  (B, KV, S, blocks), queries a chunk at a time.

The selection's block IS the pool's block (``pa_block_size``), so a selected
block is a table entry.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")
#: queries a chunk of :func:`prefill_mask`: (KV, G, 1024, J) float32 scores alive
PREFILL_QUERY_CHUNK = 1024


@dataclass(frozen=True)
class BlockSelectConfig:
    kernel_size: int
    kernel_stride: int
    block_size: int
    topk: int
    init_blocks: int
    window_size: int
    dense_len: int

    def __post_init__(self):
        if self.block_size % self.kernel_stride or self.kernel_size % self.kernel_stride:
            raise ValueError("block_size and kernel_size have to be multiples of kernel_stride")
        if self.dense_len % self.block_size:
            raise ValueError("dense_len has to be a multiple of block_size")

    @classmethod
    def from_dict(cls, d):
        return cls(**{k: int(d[k]) for k in cls.__dataclass_fields__})

    def index_rows(self, seq_len: int) -> int:
        """Windows wholly inside ``seq_len`` tokens."""
        return max((seq_len - self.kernel_size) // self.kernel_stride + 1, 0)

    @property
    def table_width(self) -> int:
        """Entries of a decode step's table: the selection, or a dense row's own blocks."""
        return max(self.topk, self.dense_len // self.block_size)


def compress_keys(k, cfg: BlockSelectConfig):
    """``k`` (B, KV, S, D) from position 0 -> index rows (B, J, KV, D) float32,
    ``J = cfg.index_rows(S)``: row ``j`` the mean of positions ``stride j ..
    stride j + kernel``."""
    B, KV, S, D = k.shape
    J = cfg.index_rows(S)
    if J == 0:
        return jnp.zeros((B, 0, KV, D), jnp.float32)
    s, n = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
    used = (J - 1) * s + cfg.kernel_size
    sums = k[:, :, :used].astype(jnp.float32).reshape(B, KV, used // s, s, D).sum(axis=3)
    rows = sum(sums[:, :, i: i + J] for i in range(n)) / cfg.kernel_size
    return jnp.swapaxes(rows, 1, 2)


def _block_scores(p, n_blocks: int, cfg: BlockSelectConfig):
    """``p`` (..., J) window masses, -inf where a window is not whole yet ->
    (..., n_blocks): the largest over the windows that overlap each block."""
    m = cfg.block_size // cfg.kernel_stride  # windows that START in a block
    back = (cfg.kernel_size - 1) // cfg.kernel_stride  # earlier ones that reach into it
    J = p.shape[-1]
    right = max(n_blocks * m - J, 0)
    lead = p.ndim - 1
    pooled = jax.lax.reduce_window(
        p, NEG_INF, jax.lax.max, (1,) * lead + (back + m,), (1,) * lead + (m,),
        ((0, 0),) * lead + ((back, right),),
    )
    return pooled[..., :n_blocks]


def _keys(q, kc, t, n_blocks: int, scale: float, cfg: BlockSelectConfig):
    """Selection keys (..., KV, n_blocks) of queries ``q`` (..., KV, G, D) at
    positions ``t`` (...,) over index rows ``kc`` (..., J, KV, D) (leading dims
    of ``kc`` broadcast): +inf a forced block, its score a visible one, -inf
    one past the query. And ``visible`` (..., 1, n_blocks)."""
    J = kc.shape[-3]
    s = jnp.einsum(
        "...kgd,...jkd->...kgj", q.astype(jnp.float32), kc.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * scale
    whole = jnp.arange(J, dtype=jnp.int32) * cfg.kernel_stride + cfg.kernel_size - 1 <= t[..., None]
    whole = whole[..., None, None, :]  # (..., 1, 1, J)
    p = jax.nn.softmax(jnp.where(whole, s, NEG_INF), axis=-1)
    p = jnp.where(whole, p, 0.0).sum(axis=-2)  # the group's sum: (..., KV, J)
    score = _block_scores(jnp.where(whole[..., 0, :], p, NEG_INF), n_blocks, cfg)
    b = jnp.arange(n_blocks, dtype=jnp.int32)
    visible = (b <= (t // cfg.block_size)[..., None])[..., None, :]
    recent = jnp.maximum(t - cfg.window_size + 1, 0) // cfg.block_size
    forced = ((b < cfg.init_blocks) | (b >= recent[..., None]))[..., None, :] & visible
    return jnp.where(forced, jnp.inf, jnp.where(visible, score, NEG_INF)), visible


def decode_tables(q, kc, positions, block_table, scale: float, cfg: BlockSelectConfig):
    """One decode step's selection.

    ``q`` (B, KV, G, D); ``kc`` (B, J, KV, D) the rows' index (the window that
    ends at this position already in it); ``positions`` (B,); ``block_table``
    (B, NB) the rows' own tables. Returns ``(tables (B, KV, W), q_pos (B, KV),
    read (B, KV), live (B,))``: ``W = cfg.table_width`` block ids a (row, KV
    head) in logical order with -1 behind them, the query's position in that
    order, and the blocks read and visible (the step's counters)."""
    B, KV = q.shape[:2]
    NB, W = block_table.shape[1], cfg.table_width
    t = positions.astype(jnp.int32)
    with jax.named_scope("attn.select"):
        keys, _ = _keys(q, kc, t, NB, scale, cfg)
        vals, idx = jax.lax.top_k(keys, min(cfg.topk, NB))
        chosen = vals > NEG_INF
        read = chosen.sum(axis=-1).astype(jnp.int32)  # (B, KV)
        order = jnp.sort(jnp.where(chosen, idx, NB), axis=-1)  # logical order, holes last
        bt = block_table.astype(jnp.int32)
        ids = jnp.take_along_axis(bt[:, None, :], jnp.minimum(order, NB - 1), axis=-1)
        sparse_tables = jnp.where(order < NB, ids, -1)
        sparse_tables = jnp.pad(
            sparse_tables, ((0, 0), (0, 0), (0, W - sparse_tables.shape[-1])), constant_values=-1
        )
        dense_tables = jnp.broadcast_to(
            jnp.pad(bt[:, :W], ((0, 0), (0, max(W - NB, 0))), constant_values=-1)[:, None, :],
            (B, KV, W),
        )
        live = t // cfg.block_size + 1
        dense = (t < cfg.dense_len)[:, None]
        tables = jnp.where(dense[..., None], dense_tables, sparse_tables)
        q_pos = jnp.where(dense, t[:, None], (read - 1) * cfg.block_size + (t % cfg.block_size)[:, None])
        read = jnp.where(dense, live[:, None], read)
    return tables, q_pos, read, live


def prefill_mask(q, kc, positions, scale: float, cfg: BlockSelectConfig, chunk=PREFILL_QUERY_CHUNK):
    """A fresh prompt's selection: ``q`` (B, KV, G, S, D), ``kc`` (B, J, KV, D)
    its own index rows, ``positions`` (B, S) -> bool (B, KV, S, S // block_size):
    the blocks each query token reads (every visible one under ``dense_len``)."""
    B, KV, G, S, D = q.shape
    NB = S // cfg.block_size
    C = min(chunk, S)
    assert S % C == 0 and S % cfg.block_size == 0, (S, C, cfg.block_size)

    def one(args):
        qc, t = args  # (B, KV, G, C, D), (B, C)
        keys, visible = _keys(jnp.moveaxis(qc, 3, 1), kc[:, None], t, NB, scale, cfg)  # (B, C, KV, NB)
        kth = jax.lax.top_k(keys, min(cfg.topk, NB))[0][..., -1:]
        chosen = (keys >= kth) & visible
        return jnp.where((t < cfg.dense_len)[..., None, None], visible, chosen)

    with jax.named_scope("attn.select"):
        qs = jnp.moveaxis(q.reshape(B, KV, G, S // C, C, D), 3, 0)
        ts = jnp.moveaxis(positions.astype(jnp.int32).reshape(B, S // C, C), 1, 0)
        mask = jax.lax.map(one, (qs, ts))  # (S // C, B, C, KV, NB)
        return jnp.moveaxis(mask, 0, 1).reshape(B, S, KV, NB).swapaxes(1, 2)
