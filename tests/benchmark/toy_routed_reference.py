"""Test-local plain reference of a routed decoder (the Mixtral block): the
dense decoder's attention, then per layer a router over all experts (softmax
in float32 -> top k -> renormalise) and the chosen experts' SwiGLU outputs
weighted and summed. Float32 ``jax.numpy``, nothing imported from ``nxdi_tpu``.
It stands where a routed configuration's ``references/<name>.py`` will: it
defines ``forward`` and, beside it, ``routing_margins``.

Weights as the app serves them: ``layers.moe.router.w (L, H, E)``,
``layers.moe.experts.{gate,up,down}_proj.w (L, E, in, out)``; attention, norms,
embedding and head as ``benchmark/references/dense_decoder.py`` has them.
"""

from __future__ import annotations

import math


def _run(params, config: dict, token_ids, renormalise: bool = True):
    """``(logits (S, vocab), margins (S,))``: margins is each position's
    smallest gap, over the layers, between the k-th and (k+1)-th router
    probability (every expert is held here, so every such pair counts)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hidden, top_k = config["hidden_size"], config["num_experts_per_tok"]
    d = hidden // n_q
    eps, theta = config["rms_norm_eps"], config["rope_theta"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(f32)

    def rope(x, pos):
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=f32) / d))
        ang = pos[:, None].astype(f32) * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
        rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
        return x * cos + rot * sin

    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(token_ids, dtype=jnp.int32)
        s = ids.shape[0]
        pos = jnp.arange(s)
        x = params["embed_tokens"][ids].astype(f32)
        margins = jnp.full((s,), jnp.inf, f32)
        for i in range(config["num_hidden_layers"]):
            p = jax.tree_util.tree_map(lambda a: a[i].astype(f32), params["layers"])
            h = rms(x, p["input_layernorm"])
            a = p["attn"]
            q = rope((h @ a["q_proj"]["w"]).reshape(s, n_q, d), pos)
            k = rope((h @ a["k_proj"]["w"]).reshape(s, n_kv, d), pos)
            v = (h @ a["v_proj"]["w"]).reshape(s, n_kv, d)
            k, v = jnp.repeat(k, n_q // n_kv, axis=1), jnp.repeat(v, n_q // n_kv, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
            scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
            ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
            x = x + ctx.reshape(s, n_q * d) @ a["o_proj"]["w"]

            h = rms(x, p["post_attention_layernorm"])
            probs = jax.nn.softmax(h @ p["moe"]["router"]["w"], axis=-1)  # (S, E)
            ranked = jnp.sort(probs, axis=-1)[:, ::-1]
            margins = jnp.minimum(margins, ranked[:, top_k - 1] - ranked[:, top_k])
            weight, chosen = jax.lax.top_k(probs, top_k)
            if renormalise:
                weight = weight / weight.sum(axis=-1, keepdims=True)
            e = p["moe"]["experts"]
            gated = jax.nn.silu(jnp.einsum("sh,ehi->sei", h, e["gate_proj"]["w"])) * jnp.einsum(
                "sh,ehi->sei", h, e["up_proj"]["w"])
            every = jnp.einsum("sei,eih->seh", gated, e["down_proj"]["w"])  # (S, E, H)
            picked = jnp.take_along_axis(every, chosen[:, :, None], axis=1)
            x = x + (picked * weight[:, :, None]).sum(axis=1)
        logits = rms(x, params["norm"]) @ params["lm_head"].astype(f32)
    return logits[:, : config["vocab_size"]], margins


def forward(params, config: dict, token_ids):
    return _run(params, config, token_ids)[0]


def forward_without_renormalisation(params, config: dict, token_ids):
    """The wrong router: top-k probabilities used as they are."""
    return _run(params, config, token_ids, renormalise=False)[0]


def routing_margins(params, config: dict, token_ids):
    return _run(params, config, token_ids)[1]
