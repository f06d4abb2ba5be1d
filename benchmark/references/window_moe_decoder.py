"""Plain reference of a decoder that mixes full and sliding-window attention
layers, each kind with its own head counts, with keys wider than values and
routed experts (MiMo-V2-Flash), as ONE CHIP'S SHARE of an expert-parallel
deployment.

The published forward pass in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: token embedding; per layer

    h = x + attention(rms(x));   y = h + ffn(rms(h))            (RMS, eps 1e-5)

Layer ``i`` is a WINDOW layer where ``hybrid_layer_pattern[i] == 1`` (the
``swa_*`` head counts and ``swa_rope_theta``), else a FULL layer; its ffn is
routed where ``moe_layer_freq[i] == 1``, else a SwiGLU of ``intermediate_size``.
Both lists are read up to ``num_hidden_layers``.

Attention: ``q, k`` of ``head_dim`` (192) a head, ``v`` of ``v_head_dim`` (128),
no bias, no q/k norm; rotate-half RoPE on the FIRST ``int(head_dim x
partial_rotary_factor)`` channels (rounded down to even), no scaling;
``v = v_proj(x) x attention_value_scale``; scores ``q.k x head_dim ** -0.5``.
A full layer is causal. A window layer lets query ``i`` see keys ``j`` with
``0 <= i - j < sliding_window``, and where ``add_swa_attention_sink_bias`` its
softmax has one more column a head, the learned ``sink[h]``, whose probability
is dropped: ``p_j = exp(s_j - m) / (sum_j exp(s_j - m) + exp(sink_h - m))``.
Queries are taken a block at a time: a full layer's block against every key
(one (heads, block, S) score tensor alive), a window layer's block against the
``block + sliding_window`` keys it can reach, so 8192 positions fit.

Router (float32): ``s = sigmoid(x W_g)`` over all experts; the top k of
``s + e_bias`` (``topk_method: noaux_tc``; no groups); weights ``s[chosen] /
sum s[chosen]`` (``norm_topk_prob``) x ``routed_scaling_factor`` (null: 1);
experts SwiGLU; no shared expert. Final RMS norm, untied output head. No
kernel, no cache, no batching, nothing imported from ``nxdi_tpu``.

Departures from the published model, the program's and this file's alike:
- the multi-token-prediction layers are no part of the served pass: left out;
- THE SHARE: the router scores all ``n_routed_experts_total`` experts and takes
  its top k among them, but only experts ``first_routed_expert ..
  first_routed_expert + n_routed_experts`` are held here, and the layer's
  routed part is the partial sum over those, with nothing standing in for the
  absent chips. The vocabulary is the slice ``vocab_size``.

Weights come in the layout the app serves them in: ``embed_tokens (V, H)``;
``segments``, a LIST of layer-stacked runs of layers of one kind and one ffn,
in depth order, each with ``input_layernorm``, ``post_attention_layernorm``,
``attn.{q,k,v,o}_proj.w`` as (in, out), ``attn.sink (n, heads)`` on window
layers, and ``mlp.{gate,up,down}_proj.w`` or ``moe.router.w (H, E_total)``,
``moe.router.e_bias (E_total,)``, ``moe.experts.{gate,up,down}_proj.w (E_held,
in, out)``; ``norm (H,)``; ``lm_head (H, V)``. One matrix, or one expert, is
upcast at a time; no second copy of the tree is kept.
"""

from __future__ import annotations

import weakref

Q_BLOCK = 128  # queries a block: (64, 128, 8192) float32 scores at a time
LENGTHS = (256, 4096, 6656, 8320)  # sequences are padded up to one of these: few shapes to compile

_last = {}  # the last sequence's (logits, margins): ``forward`` and
# ``routing_margins`` are asked for the same sequence one after the other. The
# weights are remembered by a WEAK reference to one leaf: no tree is kept alive

#: what a test may switch off to build a WRONG model (``forward_without``)
TERMS = ("sink", "value_scale", "selection_bias", "window_off_by_one")


def _dims(config: dict):
    n = config["num_hidden_layers"]
    held = config["n_routed_experts"]
    rotary = int(config["head_dim"] * float(config["partial_rotary_factor"]))
    return dict(
        layers=n,
        window_layer=[int(p) == 1 for p in config["hybrid_layer_pattern"][:n]],
        routed=[int(f) == 1 for f in config["moe_layer_freq"][:n]],
        full=(config["num_attention_heads"], config["num_key_value_heads"],
              config["head_dim"], config["v_head_dim"], float(config["rope_theta"])),
        window=(config["swa_num_attention_heads"], config["swa_num_key_value_heads"],
                config["swa_head_dim"], config["swa_v_head_dim"],
                float(config.get("swa_rope_theta", 10000.0))),
        span=int(config["sliding_window"]),
        rotary=rotary - rotary % 2,
        sink=bool(config.get("add_swa_attention_sink_bias", False)),
        value_scale=float(config.get("attention_value_scale") or 1.0),
        eps=float(config.get("layernorm_epsilon", config.get("rms_norm_eps", 1e-5))),
        top_k=config["num_experts_per_tok"], held=held,
        total=config.get("n_routed_experts_total") or held,
        first=config.get("first_routed_expert", 0) or 0,
        scaling=float(config.get("routed_scaling_factor") or 1.0),
        renorm=bool(config.get("norm_topk_prob", True)),
        bias=config.get("topk_method") == "noaux_tc",
    )


def _check(config: dict) -> None:
    if config.get("rope_scaling") and config["rope_scaling"].get("rope_type", "default") != "default":
        raise NotImplementedError("window_moe_decoder has plain RoPE only")
    if config.get("hidden_act", "silu") != "silu":
        raise NotImplementedError("window_moe_decoder has SwiGLU (silu) only")
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise NotImplementedError("window_moe_decoder has the sigmoid router only")
    if (config.get("n_group") or 1) > 1:
        raise NotImplementedError("window_moe_decoder has no grouped routing")
    if config.get("add_full_attention_sink_bias"):
        raise NotImplementedError("window_moe_decoder has a sink on window layers only")
    if config.get("n_shared_experts"):
        raise NotImplementedError("window_moe_decoder has no shared expert")


def _build(config: dict, without: frozenset):
    """The jitted pieces (six a sequence length), each upcasting only what
    it multiplies by. ``without``: the TERMS left out (a wrong model)."""
    import jax
    import jax.numpy as jnp

    d = _dims(config)
    f32 = jnp.float32
    eps, rd = d["eps"], d["rotary"]
    span = d["span"] + (1 if "window_off_by_one" in without else 0)
    value_scale = 1.0 if "value_scale" in without else d["value_scale"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(f32)

    def rope(x, pos, theta):  # x (S, heads, D): rotate-half on the first ``rd`` channels
        inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=f32) / rd))
        ang = pos[:, None].astype(f32) * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
        r = x[..., :rd]
        rot = jnp.concatenate([-r[..., rd // 2:], r[..., : rd // 2]], -1)
        return jnp.concatenate([r * cos + rot * sin, x[..., rd:]], -1)

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(f32)

    def qkv(x, p, geometry):
        heads, kv, hd, vd, theta = geometry
        a = p["attn"]
        s = x.shape[0]
        pos = jnp.arange(s)
        h = rms(x, p["input_layernorm"])
        q = rope((h @ a["q_proj"]["w"].astype(f32)).reshape(s, heads, hd), pos, theta)
        k = rope((h @ a["k_proj"]["w"].astype(f32)).reshape(s, kv, hd), pos, theta)
        v = (h @ a["v_proj"]["w"].astype(f32)).reshape(s, kv, vd) * value_scale
        # grouped heads: query head h reads kv head h // (heads / kv)
        return q.reshape(s, kv, heads // kv, hd), k, v, pos

    def finish(x, ctx, p):  # ctx (S, kv, g, vd)
        s = x.shape[0]
        return x + ctx.reshape(s, -1) @ p["attn"]["o_proj"]["w"].astype(f32)

    @jax.jit
    def full_attention(x, seg, i):
        """``x + o_proj(causal attention)``, a block of queries at a time
        against every key."""
        p = at({k: seg[k] for k in ("input_layernorm", "attn")}, i)
        q, k, v, pos = qkv(x, p, d["full"])
        s, scale = x.shape[0], float(d["full"][2]) ** -0.5
        block = min(Q_BLOCK, s)

        def one(args):
            q_blk, q_pos = args  # (block, kv, g, hd), (block,)
            scores = jnp.einsum("qngd,knd->ngqk", q_blk, k) * scale
            seen = (pos[None, :] <= q_pos[:, None])[None, None]
            prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("ngqk,knv->qngv", prob, v)

        ctx = jax.lax.map(one, (q.reshape((s // block, block) + q.shape[1:]),
                                pos.reshape(s // block, block)))
        return finish(x, ctx.reshape((s,) + ctx.shape[2:]), p)

    @jax.jit
    def window_attention(x, seg, i):
        """``x + o_proj(window attention with a sink column)``: a block of
        queries against the ``block + span`` keys it can reach."""
        p = at({k: seg[k] for k in ("input_layernorm", "attn")}, i)
        q, k, v, pos = qkv(x, p, d["window"])
        s, scale = x.shape[0], float(d["window"][2]) ** -0.5
        block = min(Q_BLOCK, s)
        reach = block + span
        k_pad = jnp.concatenate([jnp.zeros((span,) + k.shape[1:], f32), k], 0)
        v_pad = jnp.concatenate([jnp.zeros((span,) + v.shape[1:], f32), v], 0)
        use_sink = d["sink"] and "sink" not in without
        sink = p["attn"]["sink"].astype(f32).reshape(q.shape[1], q.shape[2]) if use_sink else None

        def one(args):
            q_blk, q_pos = args
            start = q_pos[0]  # keys at positions start - span .. start + block - 1
            kk = jax.lax.dynamic_slice_in_dim(k_pad, start, reach, 0)
            vv = jax.lax.dynamic_slice_in_dim(v_pad, start, reach, 0)
            k_pos = start - span + jnp.arange(reach)
            gap = q_pos[:, None] - k_pos[None, :]
            seen = ((gap >= 0) & (gap < span) & (k_pos[None, :] >= 0))[None, None]
            scores = jnp.where(seen, jnp.einsum("qngd,knd->ngqk", q_blk, kk) * scale, -jnp.inf)
            if sink is None:
                prob = jax.nn.softmax(scores, axis=-1)
            else:  # one more column a head; its probability is dropped
                col = jnp.broadcast_to(sink[:, :, None, None], scores.shape[:3] + (1,))
                prob = jax.nn.softmax(jnp.concatenate([scores, col], -1), axis=-1)[..., :-1]
            return jnp.einsum("ngqk,knv->qngv", prob, vv)

        ctx = jax.lax.map(one, (q.reshape((s // block, block) + q.shape[1:]),
                                pos.reshape(s // block, block)))
        return finish(x, ctx.reshape((s,) + ctx.shape[2:]), p)

    def swiglu(h, m):
        gated = jax.nn.silu(h @ m["gate_proj"]["w"].astype(f32)) * (h @ m["up_proj"]["w"].astype(f32))
        return gated @ m["down_proj"]["w"].astype(f32)

    @jax.jit
    def dense_ffn(x, seg, i):
        return x + swiglu(rms(x, seg["post_attention_layernorm"][i]), at(seg["mlp"], i))

    @jax.jit
    def routed_ffn(x, seg, i):
        """``(x + the held experts' partial sum, margins)``: the router over
        ALL experts, the held ones one at a time."""
        moe = seg["moe"]
        h = rms(x, seg["post_attention_layernorm"][i])
        scores = jax.nn.sigmoid(h @ moe["router"]["w"][i].astype(f32))  # (S, E_total)
        select = scores
        if d["bias"] and "selection_bias" not in without:
            select = scores + moe["router"]["e_bias"][i].astype(f32)[None, :]
        ranked, order = jax.lax.top_k(select, d["top_k"] + 1)
        chosen = order[:, :-1]
        top = jnp.take_along_axis(scores, chosen, axis=-1)  # the weights: scores alone
        weight = top / (top.sum(axis=-1, keepdims=True) + 1e-20) if d["renorm"] else top
        combine = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], chosen].set(
            weight * d["scaling"]
        )
        edge = order[:, -2:]  # the k-th and the (k+1)-th: a swap of these two is a rounding's
        counts = ((edge >= d["first"]) & (edge < d["first"] + d["held"])).any(axis=-1)
        gap = jnp.where(counts, ranked[:, -2] - ranked[:, -1], jnp.inf)

        def add_expert(e, ff):
            m = jax.tree_util.tree_map(lambda a: a[i, e], moe["experts"])
            w = jax.lax.dynamic_index_in_dim(combine, d["first"] + e, axis=1, keepdims=True)
            return ff + swiglu(h, m) * w

        return x + jax.lax.fori_loop(0, d["held"], add_expert, jnp.zeros_like(h)), gap

    @jax.jit
    def head(x, norm, out_proj):
        return rms(x, norm) @ out_proj.astype(f32)

    return dict(embed=embed, full_attention=full_attention, window_attention=window_attention,
                dense_ffn=dense_ffn, routed_ffn=routed_ffn, head=head)


_built = {}


def _pieces(config: dict, without: frozenset):
    import json

    key = (json.dumps(_dims(config), sort_keys=True), without)
    if key not in _built:
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

    _check(config)
    ids = np.asarray(token_ids, dtype=np.int32)
    key = (without, ids.tobytes())
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
    """Float32 ``(S,)``: per position the smallest gap, over the routed
    layers, between the k-th and (k+1)-th selection score (``scores +
    e_bias``), counting only a pair of which at least one expert is held here
    (``inf`` where no layer has such a pair). From this file's own hidden
    states."""
    return _run(params, config, token_ids)[1]


def forward_without(params, config: dict, token_ids, *terms):
    """A WRONG model for the tests: ``terms`` of ``TERMS`` left out (the sink,
    the value scale, the router's selection bias) or altered (the window one
    position wider)."""
    unknown = set(terms) - set(TERMS)
    if unknown:
        raise ValueError(f"unknown terms {sorted(unknown)}; known: {TERMS}")
    return _run(params, config, token_ids, frozenset(terms))[0]
