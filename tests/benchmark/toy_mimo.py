"""A toy share of MiMo-V2-Flash for the CPU tests: hidden 64, six layers
(full + dense, two window, a full, two window; the last five routed), 16
experts of which 4 are held, top 2, window 16, keys 24 and values 16 wide,
prompts and decodes longer than the window."""

import numpy as np

from toys import toy_config

FILE_KEYS = dict(
    num_hidden_layers=6, hybrid_layer_pattern=[0, 1, 1, 0, 1, 1, 0, 1], moe_layer_freq=[0, 1, 1, 1, 1, 1, 1, 1],
    num_attention_heads=4, num_key_value_heads=2, head_dim=24, v_head_dim=16,
    swa_num_attention_heads=4, swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
    sliding_window=16, sliding_window_size=16, attention_chunk_size=16, swa_rope_theta=10000,
    rope_theta=5000000, partial_rotary_factor=0.334, attention_value_scale=0.707,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    moe_intermediate_size=32, n_routed_experts=4, n_routed_experts_total=16, first_routed_expert=4,
    n_shared_experts=None, num_experts_per_tok=2, norm_topk_prob=True, scoring_func="sigmoid",
    n_group=1, topk_group=1, topk_method="noaux_tc", routed_scaling_factor=None,
    layernorm_epsilon=1e-5, hidden_act="silu", attention_bias=False,
)


def toy_share(**published):
    cfg = toy_config("mimo_v2_flash", **FILE_KEYS)
    for key in ("rms_norm_eps", "use_sliding_window"):
        del cfg[key]
    # the limits, from this toy's own readings on the CPU (seeds 7, 12, 2147483907; sinks and
    # selection bias drawn at 1.0): the program reads probe_mse <= 1.2e-6, probe_diff <= 0.006 and
    # served_gap <= 0.004 at decided positions; each of the four wrong models reads more
    cfg["benchmark"].update(
        reference="window_moe_decoder", cost_model="window_moe_decoder", pa_num_blocks=24,
        logit_mse_tolerance=4e-6, logit_tolerance=0.02, served_gap_tolerance=0.02,
        routing_margin=2e-3, logit_tolerance_undecided=0.3, undecided_share_max=0.6,
    )
    cfg.update(published)
    return cfg


def learned_terms_at_one(app, seed):
    """The harness draws every weight normal x 0.02, the window layers' sinks
    and the router's selection bias among them; a trained model's are O(1).
    Redraw those leaves at 1.0 so that a dropped one shows."""
    import jax
    import jax.numpy as jnp

    def redraw(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        if keys[-1] not in ("sink", "e_bias"):
            return leaf
        rng = np.random.default_rng([seed, len(keys), leaf.size])
        return jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)

    app.params = jax.tree_util.tree_map_with_path(redraw, app.params)
    return app
