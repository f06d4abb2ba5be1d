"""Plain reference of a decoder that mixes sliding-window (RoPE) and global
(NoPE) GQA layers in a post-norm residual path, with per-head q/k norms,
routed experts and a shared expert (K-EXAONE, ``model_type: exaone_moe``), as
ONE CHIP'S SHARE of an expert-parallel deployment.

The published forward pass in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: token embedding; per layer

    h = x + rms(attention(x));   y = h + rms(ffn(h))           (RMS, eps rms_norm_eps)

(no input norm: each sublayer's OUTPUT is normed before the residual add).

Layer ``i`` is a WINDOW layer where ``layer_types[i] == "sliding_attention"``
(``sliding_windows[i] == sliding_window``), else a FULL layer; its ffn is
routed where ``mlp_layer_types[i] == "sparse"``, else a SwiGLU of
``intermediate_size``. The lists are read up to ``num_hidden_layers``.

Attention: ``num_attention_heads`` query heads over ``num_key_value_heads``
of ``head_dim``, no bias; ``q`` and ``k`` each RMS-normed a HEAD by a learned
``head_dim``-wide weight; then, on a window layer alone, rotate-half RoPE on
all channels at ``rope_parameters.rope_theta``, no scaling; scores ``q.k x
head_dim ** -0.5``. A full layer is causal; a window layer lets query ``i``
see keys ``j`` with ``0 <= i - j < sliding_window``. Queries are taken a
block at a time: a full layer's block against every key, a window layer's
block against the ``block + sliding_window`` keys it can reach.

Router (float32): ``s = sigmoid(h W_r)`` over all experts, on the residual
stream itself; the top k of ``s + e_bias`` (DeepSeek-V3's ``noaux_tc``; no
groups, ``n_group`` 1); weights ``s[chosen] / sum s[chosen]``
(``norm_topk_prob``) x ``routed_scaling_factor``; experts and the
``num_shared_experts`` shared expert SwiGLU of ``moe_intermediate_size``.
Final RMS norm, untied output head. No kernel, no cache, no batching,
nothing imported from ``nxdi_tpu``.

Departures from the published model, the program's and this file's alike:
- the multi-token-prediction layer is no part of the served pass: left out;
- THE SHARE: the router scores all ``num_experts_total`` experts and takes its
  top k among them, but only experts ``first_routed_expert ..
  first_routed_expert + num_experts`` are held here; the layer's ffn is the
  partial sum over those plus the shared expert (whole, as on every chip), and
  the post-ffn norm takes that partial sum. The vocabulary is the slice
  ``vocab_size``;
- ASSUMED (the published ``config.json`` does not say; a checkpoint's tensor
  names would): the post-norm placement and per-head q/k norms (EXAONE 4.0's),
  RoPE on the window layers alone, the router's selection bias.

Weights come in the layout the app serves them in: ``embed_tokens (V, H)``;
``segments``, a LIST of layer-stacked runs of layers of one kind and one ffn,
in depth order, each with ``input_layernorm`` (the POST-ATTENTION norm),
``post_attention_layernorm`` (the POST-FFN norm), ``attn.{q,k,v,o}_proj.w``
as (in, out), ``attn.{q,k}_norm (n, head_dim)``, and ``mlp.{gate,up,down}_proj.w``
or ``moe.router.w (H, E_total)``, ``moe.router.e_bias (E_total,)``,
``moe.experts.{gate,up,down}_proj.w (E_held, in, out)``,
``moe.shared_expert.*.w``; ``norm (H,)``; ``lm_head (H, V)``. One matrix, or
one expert, is upcast at a time; no second copy of the tree is kept.
"""

from __future__ import annotations

import weakref

Q_BLOCK = 128  # queries a block: (64, 128, 8192) float32 scores at a time
LENGTHS = (256, 4096, 6656, 8320)  # sequences are padded up to one of these: few shapes to compile

_last = {}  # the last sequence's (logits, margins), the weights by a WEAK reference

#: what a test may alter to build a WRONG model (``forward_without``)
TERMS = ("post_norm", "pre_norm", "rope_on_full", "qk_norm", "shared_expert", "selection_bias",
         "routed_scale")


def _dims(config: dict):
    n = config["num_hidden_layers"]
    held = config["num_experts"]
    rope = config.get("rope_parameters") or {}
    return dict(
        layers=n,
        window_layer=[t == "sliding_attention" for t in config["layer_types"][:n]],
        routed=[t == "sparse" for t in config["mlp_layer_types"][:n]],
        heads=config["num_attention_heads"], kv=config["num_key_value_heads"], d=config["head_dim"],
        theta=float(rope.get("rope_theta", config.get("rope_theta", 10000.0))),
        span=int(config["sliding_window"]), eps=float(config["rms_norm_eps"]),
        top_k=config["num_experts_per_tok"], held=held,
        total=config.get("num_experts_total") or held,
        first=config.get("first_routed_expert", 0) or 0,
        scaling=float(config.get("routed_scaling_factor") or 1.0),
        renorm=bool(config.get("norm_topk_prob", True)),
        shared=int(config.get("num_shared_experts") or 0),
    )


def _check(config: dict) -> None:
    n = config["num_hidden_layers"]
    if (config.get("rope_parameters") or {}).get("rope_type", "default") != "default":
        raise NotImplementedError("exaone_moe_decoder has plain RoPE only")
    if config.get("hidden_act", "silu") != "silu":
        raise NotImplementedError("exaone_moe_decoder has SwiGLU (silu) only")
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise NotImplementedError("exaone_moe_decoder has the sigmoid router only")
    if (config.get("n_group") or 1) > 1:
        raise NotImplementedError("exaone_moe_decoder has no grouped routing")
    spans = config.get("sliding_windows", [config["sliding_window"]] * n)[:n]
    for kind, span in zip(config["layer_types"][:n], spans):
        if span != (config["sliding_window"] if kind == "sliding_attention" else 0):
            raise NotImplementedError("exaone_moe_decoder has one window, on sliding layers alone")


def _build(config: dict, without: frozenset):
    """The jitted pieces (five a sequence length), each upcasting only what
    it multiplies by. ``without``: the TERMS left out or altered (a wrong model)."""
    import jax
    import jax.numpy as jnp

    d = _dims(config)
    f32 = jnp.float32
    eps, D = d["eps"], d["d"]
    post = "post_norm" not in without and "pre_norm" not in without
    scaling = 1.0 if "routed_scale" in without else d["scaling"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(f32)

    def block_in(x, w):  # what a sublayer reads: the stream, or (a wrong model) its norm
        return rms(x, w) if "pre_norm" in without else x

    def block_out(x, out, w):  # the residual add of a sublayer's output
        return x + (rms(out, w) if post else out)

    def rope(x, pos):  # x (S, heads, D): rotate-half on every channel
        inv = 1.0 / (d["theta"] ** (jnp.arange(0, D, 2, dtype=f32) / D))
        ang = pos[:, None].astype(f32) * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
        rot = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], -1)
        return x * cos + rot * sin

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(f32)

    def qkv(x, p, rotary):
        a = p["attn"]
        s, heads, kv = x.shape[0], d["heads"], d["kv"]
        pos = jnp.arange(s)
        h = block_in(x, p["input_layernorm"])
        q = (h @ a["q_proj"]["w"].astype(f32)).reshape(s, heads, D)
        k = (h @ a["k_proj"]["w"].astype(f32)).reshape(s, kv, D)
        v = (h @ a["v_proj"]["w"].astype(f32)).reshape(s, kv, D)
        if "qk_norm" not in without:  # one weight a head's width, each head normed alone
            q, k = rms(q, a["q_norm"]), rms(k, a["k_norm"])
        if rotary:
            q, k = rope(q, pos), rope(k, pos)
        # grouped heads: query head h reads kv head h // (heads / kv)
        return q.reshape(s, kv, heads // kv, D), k, v, pos

    def finish(x, ctx, p):  # ctx (S, kv, g, D)
        out = ctx.reshape(x.shape[0], -1) @ p["attn"]["o_proj"]["w"].astype(f32)
        return block_out(x, out, p["input_layernorm"])

    def blocks(q, pos):
        s = q.shape[0]
        block = min(Q_BLOCK, s)
        return (q.reshape((s // block, block) + q.shape[1:]), pos.reshape(s // block, block))

    @jax.jit
    def full_attention(x, seg, i):
        """The causal NoPE layer, a block of queries at a time against every key."""
        p = at({k: seg[k] for k in ("input_layernorm", "attn")}, i)
        q, k, v, pos = qkv(x, p, "rope_on_full" in without)

        def one(args):
            q_blk, q_pos = args  # (block, kv, g, D), (block,)
            scores = jnp.einsum("qngd,knd->ngqk", q_blk, k) * D ** -0.5
            seen = (pos[None, :] <= q_pos[:, None])[None, None]
            prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("ngqk,knv->qngv", prob, v)

        ctx = jax.lax.map(one, blocks(q, pos))
        return finish(x, ctx.reshape((x.shape[0],) + ctx.shape[2:]), p)

    @jax.jit
    def window_attention(x, seg, i):
        """The window layer: a block of queries against the ``block + span``
        keys it can reach."""
        p = at({k: seg[k] for k in ("input_layernorm", "attn")}, i)
        q, k, v, pos = qkv(x, p, True)
        span = d["span"]
        reach = min(Q_BLOCK, x.shape[0]) + span
        k_pad = jnp.concatenate([jnp.zeros((span,) + k.shape[1:], f32), k], 0)
        v_pad = jnp.concatenate([jnp.zeros((span,) + v.shape[1:], f32), v], 0)

        def one(args):
            q_blk, q_pos = args
            start = q_pos[0]  # keys at positions start - span .. start + block - 1
            kk = jax.lax.dynamic_slice_in_dim(k_pad, start, reach, 0)
            vv = jax.lax.dynamic_slice_in_dim(v_pad, start, reach, 0)
            k_pos = start - span + jnp.arange(reach)
            gap = q_pos[:, None] - k_pos[None, :]
            seen = ((gap >= 0) & (gap < span) & (k_pos[None, :] >= 0))[None, None]
            scores = jnp.where(seen, jnp.einsum("qngd,knd->ngqk", q_blk, kk) * D ** -0.5, -jnp.inf)
            return jnp.einsum("ngqk,knv->qngv", jax.nn.softmax(scores, axis=-1), vv)

        ctx = jax.lax.map(one, blocks(q, pos))
        return finish(x, ctx.reshape((x.shape[0],) + ctx.shape[2:]), p)

    def swiglu(h, m):
        gated = jax.nn.silu(h @ m["gate_proj"]["w"].astype(f32)) * (h @ m["up_proj"]["w"].astype(f32))
        return gated @ m["down_proj"]["w"].astype(f32)

    @jax.jit
    def dense_ffn(x, seg, i):
        w = seg["post_attention_layernorm"][i]
        return block_out(x, swiglu(block_in(x, w), at(seg["mlp"], i)), w)

    @jax.jit
    def routed_y(x, seg, i):
        """``(y, margins)``: the held experts' partial sum plus the shared
        expert, BEFORE the post-ffn norm; the router over ALL experts, the held
        ones one at a time."""
        moe = seg["moe"]
        h = block_in(x, seg["post_attention_layernorm"][i])
        scores = jax.nn.sigmoid(h @ moe["router"]["w"][i].astype(f32))  # (S, E_total)
        select = scores
        if "selection_bias" not in without:
            select = scores + moe["router"]["e_bias"][i].astype(f32)[None, :]
        ranked, order = jax.lax.top_k(select, d["top_k"] + 1)
        chosen = order[:, :-1]
        top = jnp.take_along_axis(scores, chosen, axis=-1)  # the weights: scores alone
        weight = top / (top.sum(axis=-1, keepdims=True) + 1e-20) if d["renorm"] else top
        rows = jnp.arange(h.shape[0])[:, None]
        combine = jnp.zeros_like(scores).at[rows, chosen].set(weight * scaling)
        # a rounding swaps a held expert in or out only across the boundary between
        # the k-th and the (k+1)-th: a chosen one is as far from it as its score
        # from the (k+1)-th, one not chosen as the k-th from its score. Held ones
        # deeper in either side count too: most of their neighbours are not held
        held = slice(d["first"], d["first"] + d["held"])
        chosen_here = jnp.zeros(select.shape, bool).at[rows, chosen].set(True)[:, held]
        gap = jnp.where(chosen_here, select[:, held] - ranked[:, -1:], ranked[:, -2:-1] - select[:, held]).min(-1)

        def add_expert(e, ff):
            m = jax.tree_util.tree_map(lambda a: a[i, e], moe["experts"])
            wt = jax.lax.dynamic_index_in_dim(combine, d["first"] + e, axis=1, keepdims=True)
            return ff + swiglu(h, m) * wt

        y = jax.lax.fori_loop(0, d["held"], add_expert, jnp.zeros_like(h))
        if d["shared"] and "shared_expert" not in without:
            y = y + swiglu(h, at(moe["shared_expert"], i))
        return y, gap

    @jax.jit
    def routed_ffn(x, seg, i):
        y, gap = routed_y(x, seg, i)
        return block_out(x, y, seg["post_attention_layernorm"][i]), gap

    @jax.jit
    def head(x, norm, out_proj):
        return rms(x, norm) @ out_proj.astype(f32)

    return dict(embed=embed, full_attention=full_attention, window_attention=window_attention,
                dense_ffn=dense_ffn, routed_y=routed_y, routed_ffn=routed_ffn, head=head)


_built = {}


def _pieces(config: dict, without: frozenset):
    import json

    key = (json.dumps(_dims(config), sort_keys=True), without)
    if key not in _built:
        _check(config)
        _built[key] = _build(config, without)
    return _built[key]


def _walk(config: dict):
    """[(segment index, index in the segment, window layer?, routed?)] by
    depth: a segment is a run of layers of one kind and one ffn."""
    d = _dims(config)
    out, seg, at, prev = [], -1, 0, None
    for layer in range(d["layers"]):
        key = (d["window_layer"][layer], d["routed"][layer])
        if key != prev:
            seg, at, prev = seg + 1, 0, key
        out.append((seg, at) + key)
        at += 1
    return out


def _run(params, config: dict, token_ids, without=frozenset()):
    """``(logits (S, vocab), margins (S,))``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ids = np.asarray(token_ids, dtype=np.int32)
    key = (without, ids.tobytes(), id(config))
    leaf = params["norm"]
    if _last.get("key") == key and _last["leaf"]() is leaf:
        return _last["value"]
    fn = _pieces(config, without)
    n = ids.shape[0]
    # attention is causal, so what follows a position does not reach it: pad to
    # one of a few lengths (each a multiple of the query block), and the
    # pieces compile for few shapes
    length = next((g for g in LENGTHS if g >= n), -(-n // Q_BLOCK) * Q_BLOCK)
    padded = np.zeros(length, np.int32)
    padded[:n] = ids
    with jax.default_matmul_precision("highest"):
        x = fn["embed"](params["embed_tokens"], jnp.asarray(padded))
        margins = jnp.full((length,), jnp.inf, jnp.float32)
        for seg_at, i, window_layer, routed in _walk(config):
            seg, i = params["segments"][seg_at], jnp.int32(i)
            x = fn["window_attention" if window_layer else "full_attention"](x, seg, i)
            if routed:
                x, gap = fn["routed_ffn"](x, seg, i)
                margins = jnp.minimum(margins, gap)
            else:
                x = fn["dense_ffn"](x, seg, i)
        logits = fn["head"](x, params["norm"], params["lm_head"])[:n, : config["vocab_size"]]
        margins = margins[:n]
    _last.update(leaf=weakref.ref(leaf), key=key, value=(logits, margins))
    return logits, margins


def forward(params, config: dict, token_ids):
    """Float32 logits ``(S, vocab)`` of one sequence ``token_ids`` (S,)."""
    return _run(params, config, token_ids)[0]


def routing_margins(params, config: dict, token_ids):
    """Float32 ``(S,)``: per position the least, over the routed layers and the
    experts held here, of how far a held expert's selection score (``scores +
    e_bias``) lies from the boundary of the top k (above the (k+1)-th if
    chosen, under the k-th if not); ``inf`` where no layer is routed. From this
    file's own hidden states."""
    return _run(params, config, token_ids)[1]


def forward_without(params, config: dict, token_ids, *terms):
    """A WRONG model for the tests: ``post_norm`` (no norm on either sublayer's
    output), ``pre_norm`` (each sublayer's INPUT normed by its weight in place
    of its output), ``rope_on_full`` (RoPE on the full layers too),
    ``qk_norm`` (no q/k norm), ``shared_expert`` (none), ``selection_bias``
    (the top k of the scores alone), ``routed_scale`` (1.0 in place of
    ``routed_scaling_factor``)."""
    unknown = set(terms) - set(TERMS)
    if unknown:
        raise ValueError(f"unknown terms {sorted(unknown)}; known: {TERMS}")
    return _run(params, config, token_ids, frozenset(terms))[0]
