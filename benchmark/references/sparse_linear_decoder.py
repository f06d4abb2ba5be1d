"""Plain reference of a decoder that mixes block-sparse softmax attention layers
and lightning (linear) attention layers (MiniCPM-SALA), as ONE PIPELINE STAGE:
layers ``first_hidden_layer .. first_hidden_layer + num_hidden_layers`` of the
published ``num_hidden_layers_total``, with the embedding and the head.

Float32 ``jax.numpy`` at ``jax.default_matmul_precision("highest")``; no kernel,
no cache, no batching, nothing imported from ``nxdi_tpu``. With ``l`` the
PUBLISHED index of a layer and ``L`` the published depth:

    h = scale_emb * E[ids]
    h += m * Mixer_l(rms(h));  h += m * W_down(silu(W_gate x) * W_up x),  x = rms(h)
    logits = W_head rms(h) / (hidden_size / dim_model_base),   m = scale_depth / sqrt(L)

``mixer_types[l] == "lightning-attn"``: ``q, k, v = W x`` as ``lightning_nh``
heads of ``lightning_head_dim``; ``q, k`` RMS-normed a head with a learned weight
(``qk_norm``); rotate-half RoPE on every channel of q and k (``lightning_use_rope``);
a float32 state a head ``S_t = g S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(d)) S_t``
with ``g = exp(-2^(-8 (h+1) / heads) * (1 - l / (L - 1) + 1e-5))``; the heads' outputs
side by side RMS-normed as ONE vector (``use_output_norm``), times ``sigmoid(W_g x)``
(``use_output_gate``), ``W_o``.
Computed as the recurrence itself, one token a step.

``mixer_types[l] == "minicpm4"``: ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads, q and k normed as above, NO RoPE
(``attn_use_rope: false``), scale ``1 / sqrt(head_dim)``. The query at position
``t < dense_len`` attends every position ``<= t``. Past it, a KV head with its
group of query heads: compressed keys ``Kc_j = mean(k[stride j : stride j + kernel])``
for every window wholly at or before ``t``; ``p_hj = softmax_j(q_h Kc_j / sqrt(d))``;
``P_j`` its sum over the group's heads; the score of block ``b`` (``block_size``
tokens) the largest ``P_j`` over the windows that overlap it; SELECTED are the
first ``init_blocks`` blocks, the blocks that hold any of the last
``window_size`` tokens, and the best-scored others up to ``topk`` blocks in all
(every visible block while no more are visible); softmax attention over the
positions ``<= t`` of the selected blocks; then ``sigmoid(W_g x)``
(``attn_use_output_gate``) and ``W_o``. Queries go a block at a time, so one
(heads, Q_BLOCK, S) score tensor is alive and 24k positions fit.

ASSUMED (the published ``config.json`` carries none of these; who has the
checkpoint can falsify each from its tensor names and shapes):
- ``sparse_config`` (kernel 32, stride 16, block 64, topk 64, init 1, window
  2048, dense_len 8192): the values MiniCPM4's own ``config.json`` publishes;
- the ``topk`` blocks COUNT the forced ones; dense or sparse is decided a QUERY
  (by its position), so a prefix's logits do not depend on what follows it;
- the block score is a max over overlapping windows (InfLLM-V2's pooling) with
  the softmax exact, where the published kernels approximate its normaliser;
- the decay by Lightning Attention's convention, the layer factor of
  MiniMax-01's published code, both at the PUBLISHED depth and layer index;
- ``qk_norm`` on both kinds of layer; the output norm ONE RMS norm over all the
  heads' channels with a hidden-wide weight, as MiniMax-01's lightning attention
  has it (a norm a head would leave only the SIGN of ``q.k`` where a state is one
  token old: a function no finite precision holds; on the chip bf16 flipped one
  head in 300 at position 0); gates elementwise, hidden wide.

Weights come in the layout the app serves them in: ``embed_tokens (V, H)``;
``segments``, a LIST of layer-stacked runs of layers of one kind, in depth
order, each with ``input_layernorm``, ``post_attention_layernorm``,
``attn.{q,k,v,o,gate}_proj.w`` as (in, out), ``attn.{q,k}_norm (n, d)``,
``attn.o_norm (n, heads x d)`` on lightning layers, ``mlp.{gate,up,down}_proj.w``;
``norm (H,)``; ``lm_head (H, V)``. One layer is upcast at a time.

``forward`` returns the (S, vocab) logits as rows that are computed when they
are asked for (``rows[a:b]``): 24k x 73k float32 logits are 7 GB, beside the
weights no chip holds them, and the comparison reads a few thousand rows.
"""

from __future__ import annotations

import math
import weakref

Q_BLOCK = 128  # queries a block: (32, 128, 24832) float32 scores at a time
ROW_BLOCK = 2048  # rows a block of the MLP and of the head
PAD_TO = 4096  # sequences past the shortest length are padded up to a multiple
SHORT = 256

_last = {}  # the last sequence's (hidden, margins); the weights by a WEAK reference

#: what a test may alter to build a WRONG model (``forward_without``)
TERMS = ("selection", "forced_blocks", "decay_layer_factor", "gate", "nope")


def _dims(config: dict):
    n = config["num_hidden_layers"]
    first = int(config.get("first_hidden_layer", 0) or 0)
    total = int(config.get("num_hidden_layers_total") or n)
    kinds = list(config["mixer_types"])[first: first + n]
    if len(kinds) != n or set(kinds) - {"minicpm4", "lightning-attn"}:
        raise ValueError(f"mixer_types does not cover layers {first}..{first + n}")
    sp = dict(config["sparse_config"])
    if sp["block_size"] % sp["kernel_stride"] or sp["kernel_size"] % sp["kernel_stride"]:
        raise NotImplementedError("block and kernel have to be multiples of the stride")
    return dict(
        layers=n, first=first, total=total, kinds=kinds, sparse=sp,
        heads=config["num_attention_heads"], kv=config["num_key_value_heads"],
        d=config["head_dim"], lin_heads=config["lightning_nh"], lin_d=config["lightning_head_dim"],
        eps=float(config["rms_norm_eps"]), theta=float(config.get("rope_theta", 10000.0)),
        emb=float(config.get("scale_emb", 1.0)),
        m=float(config.get("scale_depth", 1.0)) / math.sqrt(total),
        head_div=float(config["hidden_size"]) / float(config.get("dim_model_base") or config["hidden_size"]),
    )


def _check(config: dict) -> None:
    want = dict(attn_use_rope=False, lightning_use_rope=True, qk_norm=True, use_output_gate=True,
                use_output_norm=True, attn_use_output_gate=True, attention_bias=False,
                hidden_act="silu", lightning_scale="1/sqrt(d)")
    for key, value in want.items():
        if config.get(key, value) != value:
            raise NotImplementedError(f"sparse_linear_decoder has {key} = {value!r} only")
    if config["lightning_nkv"] != config["lightning_nh"]:
        raise NotImplementedError("sparse_linear_decoder has ungrouped lightning heads only")


def decay_rates(config: dict, layer: int, layer_factor: bool = True):
    """``-log g`` a head of the PUBLISHED layer ``layer`` (numpy float64)."""
    import numpy as np

    heads = config["lightning_nh"]
    total = int(config.get("num_hidden_layers_total") or config["num_hidden_layers"])
    slope = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return slope * ((1.0 - layer / max(total - 1, 1) + 1e-5) if layer_factor else 1.0)


def _build(config: dict, without: frozenset):
    """The jitted pieces, each upcasting only what it multiplies by."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    d = _dims(config)
    f32, eps = jnp.float32, d["eps"]
    sp = d["sparse"]
    blk, kern, stride = sp["block_size"], sp["kernel_size"], sp["kernel_stride"]
    topk, dense_len = sp["topk"], sp["dense_len"]
    init_blocks = 0 if "forced_blocks" in without else sp["init_blocks"]
    window = 0 if "forced_blocks" in without else sp["window_size"]

    def rms(x, w):
        x = x.astype(f32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(f32)

    def rope(x, pos):  # (S, heads, D), rotate-half over every channel
        D = x.shape[-1]
        inv = 1.0 / (d["theta"] ** (jnp.arange(0, D, 2, dtype=f32) / D))
        ang = pos.astype(f32)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
        rot = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], axis=-1)
        return x * cos + rot * sin

    def in_blocks(fn, x, block):
        """``fn`` over ``x``'s rows, ``block`` at a time."""
        S = x.shape[0]
        if S <= block:
            return fn(x)
        assert S % block == 0, (S, block)
        out = jax.lax.map(fn, x.reshape((S // block, block) + x.shape[1:]))
        return out.reshape((S,) + out.shape[2:])

    def mlp(lp, h):
        g, u, dn = (lp["mlp"][k]["w"].astype(f32) for k in ("gate_proj", "up_proj", "down_proj"))
        w = lp["post_attention_layernorm"]
        return in_blocks(lambda x: (jax.nn.silu(rms(x, w) @ g) * (rms(x, w) @ u)) @ dn, h, ROW_BLOCK)

    def projections(lp, x, heads, kv, D):
        a = lp["attn"]
        S = x.shape[0]
        q = rms((x @ a["q_proj"]["w"].astype(f32)).reshape(S, heads, D), a["q_norm"])
        k = rms((x @ a["k_proj"]["w"].astype(f32)).reshape(S, kv, D), a["k_norm"])
        v = (x @ a["v_proj"]["w"].astype(f32)).reshape(S, kv, D)
        gate = 1.0 if "gate" in without else jax.nn.sigmoid(x @ a["gate_proj"]["w"].astype(f32))
        return q, k, v, gate

    def lightning_layer(lp, h, rates):
        S = h.shape[0]
        x = rms(h, lp["input_layernorm"])
        H, D = d["lin_heads"], d["lin_d"]
        q, k, v, gate = projections(lp, x, H, H, D)
        pos = jnp.arange(S)
        q, k = rope(q, pos) / math.sqrt(D), rope(k, pos)
        g = jnp.exp(-rates.astype(f32))[:, None, None]

        def step(state, qkv):
            q_t, k_t, v_t = qkv  # (H, D) each
            state = g * state + k_t[:, :, None] * v_t[:, None, :]
            return state, jnp.einsum("hd,hde->he", q_t, state)

        _, o = jax.lax.scan(step, jnp.zeros((H, D, D), f32), (q, k, v))
        o = rms(o.reshape(S, H * D), lp["attn"]["o_norm"]) * gate
        h = h + d["m"] * (o @ lp["attn"]["o_proj"]["w"].astype(f32))
        return h + d["m"] * mlp(lp, h)

    def sparse_layer(lp, h):
        """``(hidden, margins (S,))``: a position's margin is the least gap, over
        the KV heads, between its last block selected BY SCORE and its first
        unselected one (inf where nothing was left out)."""
        S = h.shape[0]
        x = rms(h, lp["input_layernorm"])
        H, KV, D = d["heads"], d["kv"], d["d"]
        G = H // KV
        q, k, v, gate = projections(lp, x, H, KV, D)
        if "nope" in without:  # the WRONG model: rope on the sparse layers
            q, k = rope(q, jnp.arange(S)), rope(k, jnp.arange(S))
        q = q.reshape(S, KV, G, D) / math.sqrt(D)
        NB = -(-S // blk)
        J = max((S - kern) // stride + 1, 0)
        m = blk // stride  # windows that START in a block
        back = (kern - 1) // stride  # and those before it that reach into it
        if J:
            sums = k[: (J - 1) * stride + kern].reshape(-1, stride, KV, D).sum(axis=1)
            kc = sum(sums[i: i + J] for i in range(kern // stride)) / kern  # (J, KV, D)
            j_end = jnp.arange(J) * stride + kern - 1  # a window's last position
            # overlap[b, j]: window j shares a position with block b
            lo = np.arange(NB)[:, None] * m - back
            overlap = jnp.asarray((np.arange(J)[None, :] >= lo) & (np.arange(J)[None, :] <= lo + back + m - 1))
        kv_pos = jnp.arange(S)
        b_of = kv_pos // blk

        def block_of_queries(args):
            qb, t = args  # (Q, KV, G, D), (Q,)
            cur = t // blk
            visible = jnp.arange(NB)[None, :] <= cur[:, None]  # (Q, NB)
            sel = jnp.broadcast_to(visible[:, None, :], (t.shape[0], KV, NB))
            margin = jnp.full(t.shape, jnp.inf, f32)
            if J and "selection" not in without:
                s = jnp.einsum("qkgd,jkd->qkgj", qb, kc)
                live = (j_end[None, :] <= t[:, None])[:, None, None, :]
                p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
                p = jnp.where(live, p, 0.0).sum(axis=2)  # (Q, KV, J): the group's sum
                p = jnp.where(live[:, :, 0], p, -jnp.inf)
                score = jnp.max(jnp.where(overlap[None, None], p[:, :, None, :], -jnp.inf), axis=-1)
                first_recent = jnp.maximum(t - window + 1, 0) // blk
                forced = (jnp.arange(NB)[None, :] < init_blocks) | (
                    (jnp.arange(NB)[None, :] >= first_recent[:, None]) & (window > 0))
                forced = (forced & visible)[:, None, :]
                key = jnp.where(forced, jnp.inf, jnp.where(visible[:, None, :], score, -jnp.inf))
                # a block's rank: how many lie strictly above it
                rank = (key[:, :, None, :] > key[:, :, :, None]).sum(axis=-1)
                chosen = (rank < topk) & visible[:, None, :]
                by_score = chosen & ~forced
                left_out = visible[:, None, :] & ~chosen
                gap = (jnp.min(jnp.where(by_score, score, jnp.inf), axis=-1)
                       - jnp.max(jnp.where(left_out, score, -jnp.inf), axis=-1))
                gap = jnp.where(by_score.any(-1) & left_out.any(-1), gap, jnp.inf).min(axis=-1)
                sparse = (t >= dense_len)[:, None, None]
                sel = jnp.where(sparse, chosen, sel)
                margin = jnp.where(t >= dense_len, gap, jnp.inf)
            mask = sel[:, :, b_of] & (kv_pos[None, None, :] <= t[:, None, None])  # (Q, KV, S)
            a = jnp.einsum("qkgd,skd->qkgs", qb, k)
            a = jax.nn.softmax(jnp.where(mask[:, :, None, :], a, -jnp.inf), axis=-1)
            return jnp.einsum("qkgs,skd->qkgd", a, v).reshape(t.shape[0], H * D), margin

        qn = min(Q_BLOCK, S)
        assert S % qn == 0, (S, qn)
        o, margins = jax.lax.map(
            block_of_queries, (q.reshape(S // qn, qn, KV, G, D), kv_pos.reshape(S // qn, qn)))
        o = o.reshape(S, H * D) * gate
        h = h + d["m"] * (o @ lp["attn"]["o_proj"]["w"].astype(f32))
        return h + d["m"] * mlp(lp, h), margins.reshape(S)

    def embed(table, ids):
        return table[ids].astype(f32) * d["emb"]

    def head(norm, w, rows):
        return in_blocks(lambda x: rms(x, norm) @ w.astype(f32), rows, ROW_BLOCK) / d["head_div"]

    return dict(
        embed=jax.jit(embed), lightning=jax.jit(lightning_layer), sparse=jax.jit(sparse_layer),
        head=jax.jit(head), dims=d,
    )


_BUILT = {}


def _pieces(config: dict, without: frozenset):
    key = (id(config), without)
    if key not in _BUILT:
        _check(config)
        _BUILT.clear()
        _BUILT[key] = (config, _build(config, without))  # the config kept: its id stays its own
    return _BUILT[key][1]


def padded_length(n: int) -> int:
    return SHORT if n <= SHORT else -(-n // PAD_TO) * PAD_TO


def _segments_layers(params, kinds):
    """``(kind, one layer's tree)`` in depth order from the stacked segments."""
    import jax

    i = 0
    for seg in params["segments"]:
        n = jax.tree_util.tree_leaves(seg)[0].shape[0]
        for j in range(n):
            yield kinds[i], jax.tree_util.tree_map(lambda a, j=j: a[j], seg)
            i += 1
    assert i == len(kinds), (i, len(kinds))


def hidden_states(params, config: dict, token_ids, without=frozenset(), first_hidden=None,
                  collect=False):
    """``(hidden (S, H), margins (S,))`` of the stage's layers over ``token_ids``
    (``first_hidden``: a hidden state to start from in the embedding's place;
    ``collect``: the hidden state after every layer as a list instead)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = _pieces(config, frozenset(without))
    d = p["dims"]
    ids = np.asarray(token_ids, dtype=np.int32)
    n = padded_length(len(ids))
    ids = np.concatenate([ids, np.zeros(n - len(ids), np.int32)])
    with jax.default_matmul_precision("highest"):
        h = p["embed"](params["embed_tokens"], ids)
        if first_hidden is not None:
            h = jnp.concatenate([jnp.asarray(first_hidden, jnp.float32),
                                 jnp.zeros((n - len(first_hidden), h.shape[1]), jnp.float32)])
        margins = jnp.full((n,), jnp.inf, jnp.float32)
        states = []
        for i, (kind, lp) in enumerate(_segments_layers(params, d["kinds"])):
            if kind == "minicpm4":
                h, layer_margins = p["sparse"](lp, h)
                margins = jnp.minimum(margins, layer_margins)
            else:
                rates = decay_rates(config, d["first"] + i, "decay_layer_factor" not in without)
                h = p["lightning"](lp, h, jnp.asarray(rates, jnp.float32))
            states.append(h)
    return (states if collect else h), margins


class Rows:
    """``(S, vocab)`` logits whose rows are computed when they are asked for."""

    def __init__(self, head, norm, w, hidden, length):
        self._head, self._norm, self._w, self._hidden = head, norm, w, hidden
        self.shape = (length, w.shape[1])

    def __getitem__(self, index):
        import jax

        rest = ()
        if isinstance(index, tuple):
            index, rest = index[0], index[1:]
        rows = self._hidden[: self.shape[0]][index]
        pad = -rows.shape[0] % ROW_BLOCK if rows.shape[0] > ROW_BLOCK else 0
        if pad:
            import jax.numpy as jnp

            rows = jnp.concatenate([rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)])
        with jax.default_matmul_precision("highest"):
            out = self._head(self._norm, self._w, rows)
        out = out[: out.shape[0] - pad] if pad else out
        return out[(slice(None),) + rest] if rest else out

    def __array__(self, dtype=None):
        import numpy as np

        return np.asarray(self[:], dtype=dtype)


def _run(params, config: dict, token_ids, without=frozenset()):
    import numpy as np

    ids = np.asarray(token_ids, dtype=np.int32)
    leaf = params["norm"]
    key = (ids.tobytes(), frozenset(without), id(config))
    if _last.get("key") == key and _last["leaf"]() is leaf:
        return _last["value"]
    hidden, margins = hidden_states(params, config, ids, without)
    _last.update(key=key, leaf=weakref.ref(leaf), value=(hidden, margins))
    return hidden, margins


def forward(params, config: dict, token_ids):
    """Float32 logits ``(S, vocab)`` of ``token_ids`` (``Rows``)."""
    return forward_without(params, config, token_ids, ())


def forward_without(params, config: dict, token_ids, without):
    """``forward`` of a WRONG model: ``without`` names the TERMS altered."""
    without = frozenset([without] if isinstance(without, str) else without)
    if without - set(TERMS):
        raise ValueError(f"unknown terms {sorted(without - set(TERMS))}; known: {TERMS}")
    hidden, _ = _run(params, config, token_ids, without)
    p = _pieces(config, frozenset(without))
    return Rows(p["head"], params["norm"], params["lm_head"], hidden, len(token_ids))


def routing_margins(params, config: dict, token_ids):
    """Per position the smallest gap, over the sparse layers and their KV heads,
    between the last block selected by its score and the first one left out
    (``inf`` in the dense regime and while every visible block is selected)."""
    _, margins = _run(params, config, token_ids)
    return margins[: len(token_ids)]
