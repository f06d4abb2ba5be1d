"""Bytes and operations of one token-generation step of a latent-attention
decoder with routed experts, as ONE CHIP'S SHARE of an expert-parallel
deployment (``references/mla_moe_decoder.py`` has the equations), from the
configuration's shapes as held. Whole share: the caller divides by the chips.

Per layer: the MLA projections (q_a, q_b, kv_a, kv_b, o_proj) and four norms,
read whole; then either a dense SwiGLU, or the router, the shared expert and
the HELD experts. A held expert's weights are read only if some row of the
batch chose it: with ``rows`` rows each choosing ``top_k`` of ``total`` experts
(uniformly, as seeded random weights route), an expert is untouched with
probability ``(1 - top_k / total) ** rows``, so ``held x (1 - that)`` experts
stream. The cache is one ``kv_lora_rank + qk_rope_head_dim`` row a token a
layer: the bytes a step needs (the pool pads the rope key to a lane tile and
stores more; that padding is the program's cost, not the work's). The
absorbed decode does, per live token per layer and per head, a dot over
``r + rope`` for the score and one over ``r`` for the weighted sum.
"""

BF16 = 2


def shapes(config):
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    r, q_r = config["kv_lora_rank"], config["q_lora_rank"]
    layers, k_dense = config["num_hidden_layers"], config.get("first_k_dense_replace", 0)
    held = config["n_routed_experts"]
    total = config.get("n_routed_experts_total") or held
    attn = (h * q_r + q_r + q_r * heads * (nope + rope) + h * (r + rope) + r
            + r * heads * (nope + v) + heads * v * h)
    expert = 3 * h * config["moe_intermediate_size"]
    return {
        "layers": layers, "routed_layers": layers - k_dense, "dense_layers": k_dense,
        "attn": attn + 4 * h,  # with the layer's four norms
        "dense_mlp": 3 * h * config["intermediate_size"],
        "router": h * total, "expert": expert,
        "shared": config.get("n_shared_experts", 0) * expert,
        "held": held, "total": total, "top_k": config["num_experts_per_tok"],
        "head": h + h * config["vocab_size"],  # final norm + output head
        "heads": heads, "latent_row": r + rope, "r": r,
    }


def experts_touched(s, rows):
    """Expected number of held experts that at least one of ``rows`` rows chose."""
    return s["held"] * (1.0 - (1.0 - s["top_k"] / s["total"]) ** rows)


def attention_flops_per_token_layer(s):
    """Absorbed decode, one live token, one layer, all heads: 2 x (r + rope)
    for the score and 2 x r for the weighted sum, per head (278 528 at 128
    heads, 512 + 64)."""
    return 2.0 * s["heads"] * (s["latent_row"] + s["r"])


def tkg_step(config, rows, live_kv_tokens):
    """One decode step of ``rows`` rows over ``live_kv_tokens`` cached tokens."""
    s = shapes(config)
    streamed = (
        s["layers"] * s["attn"] + s["dense_layers"] * s["dense_mlp"] + s["head"]
        + s["routed_layers"] * (s["router"] + s["shared"] + experts_touched(s, rows) * s["expert"])
    )
    # operations: every row through the parameters it ACTIVATES (its share of
    # the top k that falls on held experts, held / total of them on average)
    active = (
        s["layers"] * s["attn"] + s["dense_layers"] * s["dense_mlp"] + s["head"]
        + s["routed_layers"] * (s["router"] + s["shared"]
                                + s["top_k"] * s["held"] / s["total"] * s["expert"])
    )
    return {
        "bytes": streamed * BF16 + live_kv_tokens * s["layers"] * s["latent_row"] * BF16,
        "flops": 2.0 * active * rows
        + attention_flops_per_token_layer(s) * live_kv_tokens * s["layers"],
    }


def mla_decode_kernel(config, rows, live_kv_tokens):
    """The ``mla_paged_decode`` launches of one step (one a layer): the live
    latent rows once (no block padding, no lane padding: a share cannot read
    over 100 %), the queries in and the result out, and the absorbed dots."""
    s = shapes(config)
    per_row = s["heads"] * (s["latent_row"] + s["r"]) * BF16  # q_lat + q_rot in, o_lat out
    return {
        "bytes": s["layers"] * (live_kv_tokens * s["latent_row"] * BF16 + rows * per_row),
        "flops": attention_flops_per_token_layer(s) * live_kv_tokens * s["layers"],
    }
