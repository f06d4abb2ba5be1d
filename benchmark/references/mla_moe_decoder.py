"""Plain reference of a latent-attention decoder with routed experts
(openPangu-Ultra-MoE; the DeepSeek-V3 block with four norms a layer), as ONE
CHIP'S SHARE of an expert-parallel deployment.

The published forward pass in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: token embedding; per layer

    h = x + post_attention_layernorm(MLA(input_layernorm(x)))
    y = h + post_mlp_layernorm(FFN(pre_mlp_layernorm(h)))          (all RMS)

MLA: ``q = q_b(rms(q_a(x)))`` split per head into 128 no-position and 64 rope
channels; ``kv_a(x)`` gives a 512-wide latent (RMS-normed) and ONE 64-wide rope
key shared by all heads; ``kv_b(latent)`` expands to per-head 128 key and 128
value channels; rotate-half RoPE on the 64; causal softmax attention with scale
``(128 + 64) ** -0.5``; ``o_proj``. This is the NON-absorbed form, with every
position's keys and values expanded: a second derivation of what the program
computes absorbed, through its cache. FFN: the first ``first_k_dense_replace``
layers a SwiGLU of ``intermediate_size``; after them a router (sigmoid scores
over all experts, top k, renormalised, x ``routed_scaling_factor``) over SwiGLU
experts of ``moe_intermediate_size`` plus ``n_shared_experts`` shared ones, one
wide SwiGLU. Final RMS norm and output head. No kernel, no cache, no batching,
nothing imported from ``nxdi_tpu``.

Departures from the published model, the program's and this file's alike:
- the multi-token-prediction module (``num_nextn_predict_layers``) is no part
  of the forward pass that is served, and is left out;
- THE SHARE: the router scores all ``n_routed_experts_total`` experts and takes
  its top k among them, but only experts ``first_routed_expert ..
  first_routed_expert + n_routed_experts`` are held here, and the layer's
  routed part is the partial sum over those; what the absent experts would
  have added is left out, and that partial result goes on to the next layer.
  The shared expert is whole. The vocabulary is the slice ``vocab_size``.

Weights come in the layout the app serves them in: ``embed_tokens (V, H)``;
``layers`` a LIST of two layer-stacked segments, the leading dense layers and
the expert layers, each with ``attn.{q_a, q_b, kv_a, kv_b, o_proj}.w`` as
(in, out), ``attn.{q_a_norm, kv_a_norm}``, ``input_layernorm``,
``post_attention_layernorm``, ``pre_feedforward_layernorm``,
``post_feedforward_layernorm`` (the published ``pre_mlp``/``post_mlp``), and
``mlp.{gate,up,down}_proj.w`` or ``moe.router.w (H, E_total)``,
``moe.experts.{gate,up,down}_proj.w (E_held, in, out)``,
``moe.shared_expert.{gate,up,down}_proj.w``; ``norm (H,)``; ``lm_head (H, V)``.
One matrix, or one expert, is upcast at a time and attention runs in blocks of
heads, so the reference fits beside 9 GiB of weights at sequences of 2560.
"""

from __future__ import annotations

import weakref

HEAD_BLOCK = 16  # heads per attention block: (16, S, S) float32 scores at a time
LENGTHS = (256, 1024, 2560, 4096)  # sequences are padded up to one of these

_last = {}  # the last sequence's (logits, margins): ``forward`` and
# ``routing_margins`` are asked for the same sequence one after the other. The
# weights are remembered by a WEAK reference to one leaf: this file keeps no
# tree alive (a second copy of the weights does not fit beside the first)


def _dims(config: dict):
    held = config["n_routed_experts"]
    return dict(
        heads=config["num_attention_heads"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"], r=config["kv_lora_rank"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        layers=config["num_hidden_layers"], k_dense=config.get("first_k_dense_replace", 0),
        top_k=config["num_experts_per_tok"], held=held,
        total=config.get("n_routed_experts_total") or held,
        first=config.get("first_routed_expert", 0) or 0,
        scaling=float(config.get("routed_scaling_factor", 1.0)),
        renorm=bool(config.get("norm_topk_prob", True)),
    )


def _check(config: dict) -> None:
    if config.get("rope_scaling"):
        raise NotImplementedError("mla_moe_decoder has plain RoPE only")
    if config.get("hidden_act", "silu") != "silu":
        raise NotImplementedError("mla_moe_decoder has SwiGLU (silu) only")
    if config.get("q_lora_rank") is None:
        raise NotImplementedError("mla_moe_decoder has the low-rank query path only")
    if config.get("n_group") or config.get("topk_group"):
        raise NotImplementedError("mla_moe_decoder has no grouped routing")


def _build(config: dict, renormalise: bool):
    """The jitted pieces (five a sequence length), each upcasting only what
    it multiplies by."""
    import jax
    import jax.numpy as jnp

    d = _dims(config)
    f32 = jnp.float32
    H, nope, rope_d, vd, r = d["heads"], d["nope"], d["rope"], d["v"], d["r"]
    eps, scale = d["eps"], float((nope + rope_d) ** -0.5)
    block = min(HEAD_BLOCK, H)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(f32)

    def rope(x, pos):  # x (S, heads, rope_d), rotate-half
        inv = 1.0 / (d["theta"] ** (jnp.arange(0, rope_d, 2, dtype=f32) / rope_d))
        ang = pos[:, None].astype(f32) * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
        rot = jnp.concatenate([-x[..., rope_d // 2:], x[..., : rope_d // 2]], -1)
        return x * cos + rot * sin

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(f32)

    @jax.jit
    def attention(x, seg, i):
        """``x + post_attention_layernorm(MLA(input_layernorm(x)))``, the
        heads a block at a time (one (block, S, S) score tensor alive)."""
        p = at({k: seg[k] for k in ("input_layernorm", "post_attention_layernorm", "attn")}, i)
        a = p["attn"]
        s = x.shape[0]
        pos = jnp.arange(s)
        h = rms(x, p["input_layernorm"])
        q = rms(h @ a["q_a"]["w"].astype(f32), a["q_a_norm"]) @ a["q_b"]["w"].astype(f32)
        q = q.reshape(s, H, nope + rope_d)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos)], axis=-1)
        ckv = h @ a["kv_a"]["w"].astype(f32)
        latent = rms(ckv[:, :r], a["kv_a_norm"])  # (S, r)
        k_rot = rope(ckv[:, None, r:], pos)[:, 0]  # (S, rope): one key for every head
        causal = (pos[:, None] >= pos[None, :])[None]
        kv_b = a["kv_b"]["w"].reshape(r, H // block, block, nope + vd)

        def heads(args):
            """Causal attention of one block of heads: their keys and values
            expanded from the latent through their columns of kv_b."""
            q_blk, w = args  # (S, block, nope + rope), (r, block, nope + v)
            w = w.astype(f32)
            k_nope = jnp.einsum("sr,rhn->shn", latent, w[..., :nope])
            value = jnp.einsum("sr,rhv->shv", latent, w[..., nope:])
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rot[:, None, :], (s, block, rope_d))], axis=-1
            )
            scores = jnp.where(causal, jnp.einsum("qhd,khd->hqk", q_blk, k) * scale, -jnp.inf)
            return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(scores, axis=-1), value)

        q_blocks = jnp.moveaxis(q.reshape(s, H // block, block, nope + rope_d), 1, 0)
        ctx = jax.lax.map(heads, (q_blocks, jnp.moveaxis(kv_b, 1, 0)))  # (H/block, S, block, v)
        ctx = jnp.moveaxis(ctx, 0, 1).reshape(s, H * vd)
        return x + rms(ctx @ a["o_proj"]["w"].astype(f32), p["post_attention_layernorm"])

    def swiglu(h, m):
        gated = jax.nn.silu(h @ m["gate_proj"]["w"].astype(f32)) * (h @ m["up_proj"]["w"].astype(f32))
        return gated @ m["down_proj"]["w"].astype(f32)

    def ffn_norms(seg, i):
        return seg["pre_feedforward_layernorm"][i], seg["post_feedforward_layernorm"][i]

    @jax.jit
    def dense_ffn(x, seg, i):
        pre, post = ffn_norms(seg, i)
        return x + rms(swiglu(rms(x, pre), at(seg["mlp"], i)), post)

    @jax.jit
    def routed_ffn(x, seg, i):
        """``(x + post_mlp_layernorm(shared + held experts' partial sum),
        margins)``: the router over ALL experts, the held ones one at a time."""
        pre, post = ffn_norms(seg, i)
        moe = seg["moe"]
        h = rms(x, pre)
        scores = jax.nn.sigmoid(h @ moe["router"]["w"][i].astype(f32))  # (S, E_total)
        ranked, order = jax.lax.top_k(scores, d["top_k"] + 1)
        top, chosen = ranked[:, :-1], order[:, :-1]
        weight = top / (top.sum(axis=-1, keepdims=True) + 1e-20) if renormalise else top
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

        ff = swiglu(h, at(moe["shared_expert"], i)) if "shared_expert" in moe else jnp.zeros_like(h)
        ff = jax.lax.fori_loop(0, d["held"], add_expert, ff)
        return x + rms(ff, post), gap

    @jax.jit
    def head(x, norm, out_proj):
        return rms(x, norm) @ out_proj.astype(f32)

    return dict(embed=embed, attention=attention, dense_ffn=dense_ffn, routed_ffn=routed_ffn, head=head)


_built = {}


def _pieces(config: dict, renormalise: bool):
    import json

    key = (json.dumps(_dims(config), sort_keys=True), renormalise)
    if key not in _built:
        _built[key] = _build(config, renormalise)
    return _built[key]


def _segment(params, config: dict, layer: int):
    """(the layer-stacked segment holding ``layer``, its index in it)."""
    layers, k = params["layers"], _dims(config)["k_dense"]
    if not isinstance(layers, (list, tuple)):
        return layers, layer
    return (layers[0], layer) if layer < k else (layers[1], layer - k)


def _run(params, config: dict, token_ids, renormalise=None):
    """``(logits (S, vocab), margins (S,))``; ``renormalise`` None: as the
    configuration's ``norm_topk_prob`` says."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _check(config)
    ids = np.asarray(token_ids, dtype=np.int32)
    if renormalise is None:
        renormalise = _dims(config)["renorm"]
    key = (renormalise, ids.tobytes())
    leaf = params["norm"]
    if _last.get("key") == key and _last["leaf"]() is leaf:
        return _last["value"]
    d, fn = _dims(config), _pieces(config, renormalise)
    n = ids.shape[0]
    # attention is causal, so what follows a position does not reach it: pad to
    # one of a few lengths, and the pieces compile for few shapes
    padded = np.zeros(next((g for g in LENGTHS if g >= n), n), np.int32)
    padded[:n] = ids
    with jax.default_matmul_precision("highest"):
        x = fn["embed"](params["embed_tokens"], jnp.asarray(padded))
        margins = jnp.full((padded.shape[0],), jnp.inf, jnp.float32)
        for layer in range(d["layers"]):
            seg, i = _segment(params, config, layer)
            i = jnp.int32(i)
            x = fn["attention"](x, seg, i)
            if "moe" in seg:
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
    layers, between the k-th and (k+1)-th router score, counting only a pair
    of which at least one expert is held here (``inf`` where no layer has such
    a pair). From this file's own hidden states."""
    return _run(params, config, token_ids)[1]


def forward_without_renormalisation(params, config: dict, token_ids):
    """A WRONG model for the tests: the top-k weights left unnormalised."""
    return _run(params, config, token_ids, renormalise=False)[0]
