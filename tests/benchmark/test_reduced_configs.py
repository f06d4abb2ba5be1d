"""A configuration that is a chip's share of a deployment (the model-configs
guide, section 4): each key in ``reduced`` is in the file and in the manifest
alike, the file states the published value and the deployment beside it, and
the guide's floors hold. A configuration that cuts nothing passes trivially.
(``test_benchmark_loader.py::test_published_sizes_are_uncut`` predates the cut
and still asserts ``reduced == []``: the next ``benchmark`` issue re-points it
at this rule; PERF.md section 7.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells, serving_app  # noqa: E402

MANIFEST = cells.load_manifest()
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
#: what ``reduced`` may never name: a width
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
              "num_experts_per_tok", "q_lora_rank", "kv_lora_rank")


def _entry(name):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    return entry, cells.read_json(entry["file"])


@pytest.mark.parametrize("name", CONFIGS)
def test_reduced_is_the_same_in_the_file_and_the_manifest(name):
    entry, body = _entry(name)
    assert body["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert cells.NAME_RE.match(key), key
        assert key in body and key not in serving_app.BENCHMARK_KEYS, key


@pytest.mark.parametrize("name", CONFIGS)
def test_every_cut_states_the_published_value_and_the_deployment(name):
    entry, body = _entry(name)
    if not entry["reduced"]:
        return
    assert len(body["deployment"]) > 40 and "chip" in body["deployment"]
    assert sorted(body["published"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert body["published"][key] > body[key] > 0, key  # a cut, and of the stated key
        assert str(body["published"][key]) in body["published_why"][key], key


@pytest.mark.parametrize("name", CONFIGS)
def test_no_width_is_cut(name):
    entry, _ = _entry(name)
    for key in entry["reduced"]:
        assert key not in WIDTH_KEYS and not key.endswith(("_dim", "_rank")), key


@pytest.mark.parametrize("name", CONFIGS)
def test_the_guides_floors_hold(name):
    """A whole period and at least four layers after the leading dense ones,
    at least 8 routed experts in a layer that has them, at least an eighth of
    the vocabulary; leading dense layers count once."""
    entry, body = _entry(name)
    reduced, published = entry["reduced"], body.get("published", {})
    if "vocab_size" in reduced:
        assert body["vocab_size"] * 8 >= published["vocab_size"]
    if "n_routed_experts" in reduced:
        assert body["n_routed_experts"] >= 8
        # the router keeps the published width and its experts per token
        assert body["n_routed_experts_total"] == published["n_routed_experts"]
        first = body.get("first_routed_expert", 0)
        assert 0 <= first <= published["n_routed_experts"] - body["n_routed_experts"]
    if "num_hidden_layers" in reduced:
        dense = body.get("first_k_dense_replace", 0)
        assert body["num_hidden_layers"] - dense >= 4
    if "first_k_dense_replace" in reduced:
        assert body["first_k_dense_replace"] >= 1


@pytest.mark.parametrize("name", CONFIGS)
def test_a_share_fills_the_chip_it_is_cut_to(name):
    """Weights (2 bytes a parameter, from the app's own shapes) and the pool,
    against one v5e chip's 16 GB times the configuration's chips: the floor of
    a quarter, and room left for the programs' temporaries."""
    import jax

    from nxdi_tpu.models.registry import get_family

    _, body = _entry(name)
    b = body["benchmark"]
    published = {k: v for k, v in body.items() if k not in serving_app.BENCHMARK_KEYS}
    family, cfg_cls = get_family(body["model_type"])
    config = cfg_cls(serving_app.tpu_config_of(body, [256], 16), load_config=lambda: dict(published))
    app = serving_app.application_class(family)("<shapes>", config, model_family=family)
    held = sum(
        leaf.size * leaf.dtype.itemsize
        for tree in (app.build_params_struct(), app._cache_struct())
        for leaf in jax.tree_util.tree_leaves(tree)
    )
    chip = 16e9 * b["chips"]
    assert 0.25 * chip <= held <= 0.8 * chip, held / chip
