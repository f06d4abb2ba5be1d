"""Bytes and operations of one token-generation step of a decoder that mixes
full and sliding-window attention layers, with keys wider than values and
routed experts, as ONE CHIP'S SHARE of an expert-parallel deployment
(``references/window_moe_decoder.py`` has the equations), from the
configuration's shapes as held. Whole share: the caller divides by the chips.

Per layer: the four attention projections of its kind and two norms, read
whole; then a dense SwiGLU, or the router (with its selection bias) and the
HELD experts. A held expert's weights are read only if some row of the batch
chose it: with ``rows`` rows each choosing ``top_k`` of ``total`` experts
(uniformly, as seeded random weights route), ``held x (1 - (1 - top_k /
total) ** rows)`` experts stream. The cache a step reads: a FULL layer one key
row of ``head_dim`` and one value row of ``v_head_dim`` a kv head a live token
(2560 B at 4 x (192 + 128) in bf16; the pool pads the key row to a lane tile
and stores more: that padding is the program's cost, not the work's); a
WINDOW layer the same of ITS kv heads (5120 B at 8) for the last
``sliding_window`` tokens of each row and no more, whatever the context.
"""

BF16 = 2


def shapes(config):
    n = config["num_hidden_layers"]
    window = [int(p) == 1 for p in config["hybrid_layer_pattern"][:n]]
    routed = [int(f) == 1 for f in config["moe_layer_freq"][:n]]
    h = config["hidden_size"]
    held = config["n_routed_experts"]

    def kind(prefix):
        heads, kv = config[prefix + "num_attention_heads"], config[prefix + "num_key_value_heads"]
        d, v = config[prefix + "head_dim"], config[prefix + "v_head_dim"]
        sink = heads if prefix and config.get("add_swa_attention_sink_bias") else 0
        return {
            "heads": heads, "kv": kv, "d": d, "v": v,
            "attn": h * heads * d + h * kv * d + h * kv * v + heads * v * h + sink + 2 * h,
        }

    return {
        "layers": n, "window_layers": sum(window), "full_layers": n - sum(window),
        "routed_layers": sum(routed), "dense_layers": n - sum(routed),
        "full": kind(""), "window": kind("swa_"), "span": int(config["sliding_window"]),
        "dense_mlp": 3 * h * config["intermediate_size"],
        "router": h * (config.get("n_routed_experts_total") or held)
        + (config.get("n_routed_experts_total") or held),
        "expert": 3 * h * config["moe_intermediate_size"],
        "held": held, "total": config.get("n_routed_experts_total") or held,
        "top_k": config["num_experts_per_tok"],
        "head": h + h * config["vocab_size"],  # final norm + output head
    }


def experts_touched(s, rows):
    """Expected number of held experts that at least one of ``rows`` rows chose."""
    return s["held"] * (1.0 - (1.0 - s["top_k"] / s["total"]) ** rows)


def row_bytes(kind):
    """One cached token of one layer of ``kind``: a key and a value a kv head."""
    return kind["kv"] * (kind["d"] + kind["v"]) * BF16


def attention_flops_per_token(kind):
    """One attended token, one layer, all query heads: 2 x d for the score and
    2 x v for the weighted sum, per head (40 960 at 64 heads, 192 + 128)."""
    return 2.0 * kind["heads"] * (kind["d"] + kind["v"])


def window_tokens(s, rows, live_kv_tokens):
    """Tokens the window layers attend in a step: each row its last ``span``."""
    return min(live_kv_tokens, rows * s["span"])


def tkg_step(config, rows, live_kv_tokens):
    """One decode step of ``rows`` rows over ``live_kv_tokens`` cached tokens."""
    s = shapes(config)
    attn = s["full_layers"] * s["full"]["attn"] + s["window_layers"] * s["window"]["attn"]
    fixed = attn + s["dense_layers"] * s["dense_mlp"] + s["head"] + s["routed_layers"] * s["router"]
    streamed = fixed + s["routed_layers"] * experts_touched(s, rows) * s["expert"]
    # operations: every row through the parameters it ACTIVATES (its share of
    # the top k that falls on held experts, held / total of them on average)
    active = fixed + s["routed_layers"] * s["top_k"] * s["held"] / s["total"] * s["expert"]
    near = window_tokens(s, rows, live_kv_tokens)
    return {
        "bytes": streamed * BF16
        + s["full_layers"] * live_kv_tokens * row_bytes(s["full"])
        + s["window_layers"] * near * row_bytes(s["window"]),
        "flops": 2.0 * active * rows
        + s["full_layers"] * live_kv_tokens * attention_flops_per_token(s["full"])
        + s["window_layers"] * near * attention_flops_per_token(s["window"]),
    }


def paged_decode_kernel(config, rows, live_kv_tokens):
    """The ``paged_attention_decode`` launches of one step (one a FULL layer;
    the window layers' decode is no launch of it): the live key and value
    rows once (no block padding, no lane padding: a share cannot read over
    100 %), the queries in and the result out, and the two dots."""
    s = shapes(config)
    full = s["full"]
    per_row = full["heads"] * (full["d"] + full["v"]) * BF16  # q in, o out
    return {
        "bytes": s["full_layers"] * (live_kv_tokens * row_bytes(full) + rows * per_row),
        "flops": s["full_layers"] * live_kv_tokens * attention_flops_per_token(full),
    }
