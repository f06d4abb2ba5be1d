"""Bytes and operations of one token-generation step of a decoder that mixes
sliding-window (RoPE) and global (NoPE) GQA layers in a post-norm residual
path, with routed experts and a shared expert (K-EXAONE), as ONE CHIP'S SHARE
of an expert-parallel deployment (``references/exaone_moe_decoder.py`` has
the equations), from the configuration's shapes as held. Whole share: the
caller divides by the chips.

Per layer, read whole: the four attention projections, the per-head q and k
norms and the two post-norms; then a dense SwiGLU of ``intermediate_size``,
or the router (with its selection bias), the shared expert and the HELD
experts a row of the batch chose: with ``rows`` rows each choosing ``top_k``
of ``total`` experts (uniformly, as seeded random weights route), ``held x (1
- (1 - top_k / total) ** rows)`` of them stream. The cache a step reads: a
FULL layer one key row and one value row a kv head a live token (4096 B at 8
x (128 + 128) in bf16); a WINDOW layer the same for the last
``sliding_window`` tokens of each row and no more, whatever the context.
"""

BF16 = 2


def shapes(config):
    n = config["num_hidden_layers"]
    window = [t == "sliding_attention" for t in config["layer_types"][:n]]
    routed = [t == "sparse" for t in config["mlp_layer_types"][:n]]
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    held = config["num_experts"]
    total = config.get("num_experts_total") or held
    expert = 3 * h * config["moe_intermediate_size"]
    return {
        "window_layers": sum(window), "full_layers": n - sum(window),
        "routed_layers": sum(routed), "dense_layers": n - sum(routed),
        "heads": heads, "kv": kv, "d": d, "span": int(config["sliding_window"]),
        # q, k, v, o; the q and k norms; the two post-norms
        "attn": h * heads * d + 2 * h * kv * d + heads * d * h + 2 * d + 2 * h,
        "dense_mlp": 3 * h * config["intermediate_size"],
        "router": h * total + total,
        "shared": int(config.get("num_shared_experts") or 0) * expert,
        "expert": expert, "held": held, "total": total, "top_k": config["num_experts_per_tok"],
        "head": h + h * config["vocab_size"],  # final norm + output head
    }


def experts_touched(s, rows):
    """Expected number of held experts that at least one of ``rows`` rows chose."""
    return s["held"] * (1.0 - (1.0 - s["top_k"] / s["total"]) ** rows)


def row_bytes(s):
    """One cached token of one layer: a key and a value a kv head."""
    return s["kv"] * 2 * s["d"] * BF16


def attention_flops_per_token(s):
    """One attended token, one layer, all query heads: 2 x d for the score and
    2 x d for the weighted sum, per head (32 768 at 64 heads of 128)."""
    return 2.0 * s["heads"] * 2 * s["d"]


def window_tokens(s, rows, live_kv_tokens):
    """Tokens a window layer attends in a step: each row its last ``span``."""
    return min(live_kv_tokens, rows * s["span"])


def tkg_step(config, rows, live_kv_tokens):
    """One decode step of ``rows`` rows over ``live_kv_tokens`` cached tokens."""
    s = shapes(config)
    layers = s["window_layers"] + s["full_layers"]
    fixed = (layers * s["attn"] + s["dense_layers"] * s["dense_mlp"] + s["head"]
             + s["routed_layers"] * (s["router"] + s["shared"]))
    streamed = fixed + s["routed_layers"] * experts_touched(s, rows) * s["expert"]
    # operations: every row through the parameters it ACTIVATES (its share of
    # the top k that falls on held experts, held / total of them on average)
    active = fixed + s["routed_layers"] * s["top_k"] * s["held"] / s["total"] * s["expert"]
    near = window_tokens(s, rows, live_kv_tokens)
    attended = s["full_layers"] * live_kv_tokens + s["window_layers"] * near
    return {
        "bytes": streamed * BF16 + attended * row_bytes(s),
        "flops": 2.0 * active * rows + attended * attention_flops_per_token(s),
    }


def paged_decode_kernel(config, rows, live_kv_tokens):
    """The ``paged_attention_decode`` launches of one step (one a FULL layer;
    the window layers' decode is no launch of it): the live key and value
    rows once (no block padding: a share cannot read over 100 %), the queries
    in and the result out, and the two dots."""
    s = shapes(config)
    per_row = s["heads"] * 2 * s["d"] * BF16  # q in, o out
    return {
        "bytes": s["full_layers"] * (live_kv_tokens * row_bytes(s) + rows * per_row),
        "flops": s["full_layers"] * live_kv_tokens * attention_flops_per_token(s),
    }
