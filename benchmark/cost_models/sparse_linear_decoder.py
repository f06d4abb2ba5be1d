"""Bytes and operations of one token-generation step of a decoder that mixes
block-sparse attention layers and lightning linear-attention layers
(``references/sparse_linear_decoder.py`` has the equations), as ONE PIPELINE
STAGE: the layers held, with the embedding and the head, from the
configuration's shapes. Whole stage: the caller divides by the chips.

Per layer: the mixer's five projections (q, k, v, o and the output gate), its
norms, and the SwiGLU, read whole. The cache a step reads: a SPARSE layer, a
row and KV head, the keys and values of the blocks it SELECTS (at most ``topk``
blocks of ``block_size`` tokens past ``dense_len``, every live token under it:
512 B a token a KV head in bf16 at 128 + 128) and the live rows of its index of
compressed keys (one row of ``head_dim`` a ``kernel_stride`` tokens a KV head);
a LIGHTNING layer its float32 state a row, read and written once
(2 x heads x d x d x 4 B: 4 MiB a row a layer), whatever the context.
"""

BF16, F32 = 2, 4


def shapes(config):
    n = config["num_hidden_layers"]
    first = config.get("first_hidden_layer", 0) or 0
    kinds = list(config["mixer_types"])[first: first + n]
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    lh, ld = config["lightning_nh"], config["lightning_head_dim"]
    mlp = 3 * h * config["intermediate_size"]
    sp = config["sparse_config"]
    return {
        "sparse_layers": kinds.count("minicpm4"), "lightning_layers": kinds.count("lightning-attn"),
        "heads": heads, "kv": kv, "d": d, "lin_heads": lh, "lin_d": ld,
        # q, o and the gate hidden wide; k and v the KV heads'; two norms a layer and the head norms
        "sparse": 3 * h * heads * d + 2 * h * kv * d + 2 * h + 2 * d + mlp,
        "lightning": 5 * h * lh * ld + 2 * h + 3 * ld + mlp,
        "head": h + h * config["vocab_size"],  # final norm + output head
        "block": sp["block_size"], "topk": sp["topk"], "dense_len": sp["dense_len"],
        "stride": sp["kernel_stride"],
    }


def selected_tokens(s, rows, live_kv_tokens):
    """Tokens a KV head of a sparse layer attends in a step, over the rows: a
    row past ``dense_len`` its ``topk`` blocks, the last of them half full on
    the average; a row under it what it holds."""
    if not rows:
        return 0.0
    a_row = live_kv_tokens / rows
    if a_row > s["dense_len"]:
        a_row = min(a_row, s["topk"] * s["block"] - s["block"] / 2.0)
    return rows * a_row


def tkg_step(config, rows, live_kv_tokens):
    """One decode step of ``rows`` rows over ``live_kv_tokens`` cached tokens."""
    s = shapes(config)
    weights = s["sparse_layers"] * s["sparse"] + s["lightning_layers"] * s["lightning"] + s["head"]
    near = selected_tokens(s, rows, live_kv_tokens)
    row = s["kv"] * 2 * s["d"] * BF16  # a token's key and value, the KV heads of a layer
    index = live_kv_tokens / s["stride"] * s["kv"] * s["d"] * BF16
    state = rows * s["lin_heads"] * s["lin_d"] * s["lin_d"] * F32
    return {
        "bytes": weights * BF16 + s["sparse_layers"] * (near * row + index)
        + s["lightning_layers"] * 2 * state,
        "flops": 2.0 * weights * rows
        + s["sparse_layers"] * (near * 4.0 * s["heads"] * s["d"]
                                + live_kv_tokens / s["stride"] * 2.0 * s["heads"] * s["d"])
        + s["lightning_layers"] * rows * 4.0 * s["lin_heads"] * s["lin_d"] * s["lin_d"],
    }


def paged_decode_kernel(config, rows, live_kv_tokens):
    """The ``paged_attention_decode`` launches of one step (one a SPARSE layer,
    over the compact tables of the selection): the SELECTED live keys and values
    once (512 B a token a KV head; an unselected block is no work, so a dense
    read cannot pass for one near its roofline), the queries in and the result
    out, and the two dots."""
    s = shapes(config)
    near = selected_tokens(s, rows, live_kv_tokens)
    per_row = 2 * s["heads"] * s["d"] * BF16  # q in, o out
    return {
        "bytes": s["sparse_layers"] * (near * s["kv"] * 2 * s["d"] * BF16 + rows * per_row),
        "flops": s["sparse_layers"] * near * 4.0 * s["heads"] * s["d"],
    }
