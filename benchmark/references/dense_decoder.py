"""Plain reference of the Llama-lineage dense decoder (Qwen2/2.5, Mistral).

The published forward pass in straightforward float32 ``jax.numpy``: token
embedding, then per layer RMSNorm -> q/k/v projections (with biases where the
family has them) -> rotate-half RoPE -> causal grouped-query attention ->
output projection -> residual -> RMSNorm -> SwiGLU MLP -> residual; a final
RMSNorm and the output head (the embedding matrix when the model ties them).
No kernel, no cache, no batching, nothing shared with ``nxdi_tpu``; matmuls at
``jax.default_matmul_precision("highest")``, because a float32 matmul on a TPU
otherwise runs in bf16 passes.

Weights come in the layout the app serves them in (that much it has to know):
``embed_tokens (V, H)``; ``layers`` stacked on a leading layer axis with
``attn.{q,k,v,o}_proj.w`` as (in, out) and optional ``.b``, ``mlp.{gate,up,
down}_proj.w`` (in, out), ``input_layernorm``/``post_attention_layernorm``
(H,); ``norm (H,)``; ``lm_head (H, V)`` unless tied. One layer is upcast at a
time, so the reference fits beside the loaded app.

Departures from the published models: none in the mathematics. Sliding-window
attention is not implemented: both configurations run with it off
(``use_sliding_window`` false / ``sliding_window`` null), and a configuration
that asks for it is refused.
"""

from __future__ import annotations

import math


def _check(config: dict) -> None:
    if config.get("use_sliding_window") or (
        config.get("model_type") == "mistral" and config.get("sliding_window")
    ):
        raise NotImplementedError("dense_decoder has no sliding-window attention")
    if config.get("rope_scaling"):
        raise NotImplementedError("dense_decoder has plain RoPE only")
    if config.get("hidden_act", "silu") != "silu":
        raise NotImplementedError("dense_decoder has SwiGLU (silu) only")


def forward(params, config: dict, token_ids):
    """Float32 logits ``(S, vocab)`` of one sequence ``token_ids`` (S,)."""
    import jax
    import jax.numpy as jnp

    _check(config)
    f32 = jnp.float32
    n_q = config["num_attention_heads"]
    n_kv = config["num_key_value_heads"]
    hidden = config["hidden_size"]
    d = config.get("head_dim") or hidden // n_q
    eps = config["rms_norm_eps"]
    theta = config["rope_theta"]
    n_layers = config["num_hidden_layers"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(f32)

    def rope(x, pos):  # x (S, heads, d)
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=f32) / d))
        ang = pos[:, None].astype(f32) * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
        rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
        return x * cos + rot * sin

    def linear(x, p):
        y = x @ p["w"].astype(f32)
        return y + p["b"].astype(f32) if "b" in p else y

    @jax.jit
    def layer(x, layers, index):
        p = jax.tree_util.tree_map(lambda a: a[index], layers)
        s = x.shape[0]
        pos = jnp.arange(s)
        h = rms(x, p["input_layernorm"])
        a = p["attn"]
        q = rope(linear(h, a["q_proj"]).reshape(s, n_q, d), pos)
        k = rope(linear(h, a["k_proj"]).reshape(s, n_kv, d), pos)
        v = linear(h, a["v_proj"]).reshape(s, n_kv, d)
        k = jnp.repeat(k, n_q // n_kv, axis=1)
        v = jnp.repeat(v, n_q // n_kv, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        causal = pos[:, None] >= pos[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + linear(ctx.reshape(s, n_q * d), a["o_proj"])
        h = rms(x, p["post_attention_layernorm"])
        m = p["mlp"]
        gated = jax.nn.silu(linear(h, m["gate_proj"])) * linear(h, m["up_proj"])
        return x + linear(gated, m["down_proj"])

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(f32)

    @jax.jit
    def head(x, norm, out_proj):
        return rms(x, norm) @ out_proj.astype(f32)

    @jax.jit
    def head_tied(x, norm, table):
        return rms(x, norm) @ table.astype(f32).T

    with jax.default_matmul_precision("highest"):
        x = embed(params["embed_tokens"], jnp.asarray(token_ids, dtype=jnp.int32))
        for i in range(n_layers):
            x = layer(x, params["layers"], jnp.int32(i))
        if config.get("tie_word_embeddings"):
            logits = head_tied(x, params["norm"], params["embed_tokens"])
        else:
            logits = head(x, params["norm"], params["lm_head"])
    return logits[:, : config["vocab_size"]]
