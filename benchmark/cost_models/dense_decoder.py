"""Bytes and operations one token-generation step of a dense GQA decoder
(embedding, per layer q/k/v/o + SwiGLU + two norms, final norm, output head)
has to move and do, from the configuration's published shapes alone. Whole
model: the caller divides by the chips. q/k/v biases, where a family has them,
are a millionth of the bytes and are not counted."""

BF16 = 2


def shapes(config):
    h = config["hidden_size"]
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config.get("head_dim") or h // n_q
    inter, layers, vocab = config["intermediate_size"], config["num_hidden_layers"], config["vocab_size"]
    per_layer = h * n_q * d + 2 * h * n_kv * d + n_q * d * h + 3 * h * inter + 2 * h
    return {
        # parameters a decode step reads whole: every layer, the final norm and
        # the output head (the embedding matrix when tied). The input embedding
        # is a gather of one row per token, counted with the activations: not.
        "streamed_params": layers * per_layer + h + h * vocab,
        "kv_bytes_per_token": layers * 2 * n_kv * d * BF16,
        "total_params": layers * per_layer + h + h * vocab * (1 if config.get("tie_word_embeddings") else 2),
    }


def tkg_step(config, rows, live_kv_tokens):
    """One decode step of ``rows`` rows over ``live_kv_tokens`` cached tokens:
    the weights once plus the live KV once; 2 x parameters x rows operations."""
    s = shapes(config)
    return {
        "bytes": s["streamed_params"] * BF16 + live_kv_tokens * s["kv_bytes_per_token"],
        "flops": 2.0 * s["streamed_params"] * rows,
    }
