"""K-EXAONE's expert layer as one chip's share (the model-configs guide, section
4), on the plain reference ``benchmark/references/exaone_moe_decoder.py``: the
FFN outputs of all the shares, taken BEFORE the post-FFN norm and with the
shared expert (which every chip computes alike) counted once, add up to the
uncut layer's. After the norm they do not: there each chip norms its own
partial sum, which is why the deployment combines the partial sums first and
why this test compares ``y``."""

import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

from toy_exaone import toy_share  # noqa: E402

H, E, I, S = 64, 16, 32, 48


def _reference():
    path = os.path.join(ROOT, "benchmark", "references", "exaone_moe_decoder.py")
    spec = importlib.util.spec_from_file_location("exaone_moe_decoder_under_test", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def _layer(seed):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3, jnp.float32)  # noqa: E731
    mlp = lambda *lead: {"gate_proj": {"w": draw(*lead, H, I)}, "up_proj": {"w": draw(*lead, H, I)},  # noqa: E731
                         "down_proj": {"w": draw(*lead, I, H)}}
    seg = {"post_attention_layernorm": jnp.asarray(rng.standard_normal((1, H)), jnp.float32),
           "moe": {"router": {"w": draw(1, H, E), "e_bias": draw(1, E)}, "experts": mlp(1, E),
                   "shared_expert": mlp(1)}}
    return seg, draw(S, H)


def _part(ref, seg, x, first, held, piece="routed_y", **published):
    """What the layer of the chip holding experts ``first .. first + held``
    returns: ``routed_y`` its FFN output before the norm, ``routed_ffn`` the
    residual stream after it."""
    cfg = toy_share(num_experts=held, first_routed_expert=first, **published)
    part = {**seg, "moe": {**seg["moe"], "experts": {
        k: {"w": v["w"][:, first:first + held]} for k, v in seg["moe"]["experts"].items()}}}
    return ref._build(cfg, frozenset())[piece](x, part, jnp.int32(0))[0]


@pytest.mark.parametrize("chips", [4, 8])
def test_the_shares_ffn_outputs_before_the_norm_add_up_to_the_whole_layers(chips):
    ref = _reference()
    seg, x = _layer(3)
    held = E // chips
    whole = _part(ref, seg, x, 0, E)
    shared = _part(ref, seg, x, 0, held) - _part(ref, seg, x, 0, held, num_shared_experts=0)
    shares = sum(_part(ref, seg, x, first, held) for first in range(0, E, held)) - (chips - 1) * shared
    assert float(jnp.abs(whole - shared).max()) > 0.05 and float(jnp.abs(shared).max()) > 0.05
    np.testing.assert_allclose(np.asarray(shares), np.asarray(whole), atol=5e-5)


def test_after_the_post_ffn_norm_the_shares_do_not_add_up():
    ref = _reference()
    seg, x = _layer(4)
    whole = _part(ref, seg, x, 0, E, "routed_ffn") - x
    parts = [_part(ref, seg, x, first, 4, "routed_ffn") - x for first in range(0, E, 4)]
    # each share's normed output has the whole layer's scale: their sum is about four times it
    assert float(jnp.abs(sum(parts) - whole).max()) > 1.0
