"""A toy share of K-EXAONE for the CPU tests: hidden 64, five layers of the LLLG
pattern (window + dense, two window, a full, a window; the last four routed),
4 query heads over 2 KV heads of 16, a window of 16 (shorter than a block of
the pool), 16 experts of which 4 are held (from expert 4 on), top 2 x 2.5, one
shared expert; prompts and decodes several windows long."""

import numpy as np

from toys import toy_config

LLLG = ["sliding_attention"] * 3 + ["full_attention"]

FILE_KEYS = dict(
    num_hidden_layers=5, layer_types=LLLG * 2, mlp_layer_types=["dense"] + ["sparse"] * 7,
    sliding_windows=[0 if t == "full_attention" else 16 for t in LLLG * 2], sliding_window=16,
    sliding_window_pattern="LLLG", first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, rms_norm_eps=1e-5,
    hidden_act="silu", num_experts=4, num_experts_total=16, first_routed_expert=4, n_routed_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=32, num_shared_experts=1, norm_topk_prob=True, scoring_func="sigmoid",
    n_group=1, topk_group=1, routed_scaling_factor=2.5, tie_word_embeddings=False,
)


def toy_share(**published):
    cfg = toy_config("exaone_moe", **FILE_KEYS)
    for key in ("rope_theta", "use_sliding_window"):
        del cfg[key]
    # the limits, from this toy's own readings on the CPU (seeds 7, 12, 2147483907; norms, selection
    # bias and router drawn by ``learned_terms_at_one``): the program reads probe_mse <= 5e-6,
    # probe_diff <= 0.017 and served_gap <= 0.005 at decided positions; the seven wrong models of
    # tests/integration/test_exaone_moe_paged.py read probe_mse >= 3.6e-4, probe_diff >= 0.13 and
    # served_gap >= 0.10. Each limit near the geometric middle of the two
    cfg["benchmark"].update(
        reference="exaone_moe_decoder", cost_model="exaone_moe_decoder", pa_num_blocks=24,
        logit_mse_tolerance=4e-5, logit_tolerance=0.05, served_gap_tolerance=0.025,
        routing_margin=2e-3, logit_tolerance_undecided=0.3, undecided_share_max=0.6,
    )
    cfg.update(published)
    return cfg


def learned_terms_at_one(app, seed):
    """The harness draws every weight normal x 0.02 and the norms at 1, the
    router's selection bias among the weights; a trained model's norms and bias
    are O(1) and various, and its router's logits O(1). Redraw the layers'
    norms (the two post-norms and the q/k norms) and the selection bias at 1.0,
    so that a dropped or misplaced one shows, and the router at ``1 /
    sqrt(hidden)``, so that its scores move the choice as much as the bias does
    and the held experts are chosen on every seed."""
    import jax
    import jax.numpy as jnp

    def redraw(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        router = keys[-2:] == ["router", "w"]
        if "segments" not in keys or not router and keys[-1] not in (
                "e_bias", "q_norm", "k_norm", "input_layernorm", "post_attention_layernorm"):
            return leaf
        rng = np.random.default_rng([seed, len(keys), leaf.size, sum(map(ord, "".join(map(str, keys[-2:]))))])
        scale = 1.0 / np.sqrt(leaf.shape[-2]) if router else 1.0
        return jnp.asarray(rng.standard_normal(leaf.shape) * scale, leaf.dtype)

    app.params = jax.tree_util.tree_map_with_path(redraw, app.params)
    return app
