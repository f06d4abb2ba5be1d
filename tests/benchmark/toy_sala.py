"""A toy stage of MiniCPM-SALA for the CPU tests: hidden 64, an 8-layer model at
the published ratio of kinds of which layers 2..5 are held (sparse, lightning,
sparse, sparse... see ``MIXERS``), 4 query heads over 2 KV heads of 16, 4
lightning heads of 16, and a toy ``sparse_config`` (dense_len 64, block 8,
kernel 4 / stride 2, topk 6, window 16), so that prompts and decodes cross
``dense_len``, several blocks and several index windows."""

import numpy as np

from toys import toy_config

S, L = "minicpm4", "lightning-attn"
MIXERS = [S, L, L, L, S, L, L, S]
SPARSE_CONFIG = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=6, init_blocks=1,
                     window_size=16, dense_len=64)
FILE_KEYS = dict(
    num_hidden_layers=4, first_hidden_layer=4, num_hidden_layers_total=8, mixer_types=MIXERS,
    sparse_config=SPARSE_CONFIG, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16, lightning_scale="1/sqrt(d)",
    lightning_use_rope=True, attn_use_rope=False, qk_norm=True, use_output_gate=True,
    use_output_norm=True, attn_use_output_gate=True, attention_bias=False, hidden_act="silu",
    rope_theta=10000, scale_emb=12, scale_depth=1.4, dim_model_base=16, mup_denominator=32,
    tie_word_embeddings=False, rms_norm_eps=1e-6,
)


def toy_stage(**published):
    cfg = toy_config("minicpm_sala", **FILE_KEYS)
    for key in ("sliding_window", "use_sliding_window"):
        del cfg[key]
    cfg["benchmark"].update(
        reference="sparse_linear_decoder", cost_model="sparse_linear_decoder",
        pa_block_size=8, pa_num_blocks=160, seq_len=256,
        # the limits, from this toy's own readings on the CPU (seeds 7, 12, 2147483907; the mixers'
        # projections drawn at 1 / sqrt(fan in)): the program reads probe_mse <= 3e-7, probe_diff
        # <= 0.005, served_gap <= 0.0065 at decided positions (a block swapped at an undecided one
        # reaches later ones through the next layers' keys and the state) and <= 0.018 at undecided
        # ones; the five wrong models read probe_mse >= 1.3e-4 (decay, gate, rope: the 64-token
        # probe lies under dense_len) or served_gap >= 0.015 at decided positions (selection,
        # forced blocks); 0.01 is the geometric middle of the two served readings
        logit_mse_tolerance=4e-6, logit_tolerance=0.03, served_gap_tolerance=0.01,
        routing_margin=2e-3, logit_tolerance_undecided=0.06, undecided_share_max=0.6,
    )
    cfg.update(published)
    return cfg


def learned_terms_at_one(app, seed):
    """The harness draws every weight normal x 0.02; a trained model's gates and
    projections are O(1) on normed inputs. Redraw the mixers' projections at
    ``1 / sqrt(fan in)`` so that keys, gates and decays matter as they would."""
    import jax
    import jax.numpy as jnp

    def redraw(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        if "attn" not in keys or keys[-1] != "w":
            return leaf
        rng = np.random.default_rng([seed, len(keys), leaf.size, sum(map(ord, keys[-2]))])
        return jnp.asarray(rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2]), leaf.dtype)

    app.params = jax.tree_util.tree_map_with_path(redraw, app.params)
    return app
