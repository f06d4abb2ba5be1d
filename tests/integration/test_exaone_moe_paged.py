"""K-EXAONE on the paged path: the full (NoPE) layer's rows in the block pool,
the window (RoPE) layers' in ring rows a slot beside it, through
``InferenceEngine`` and ``BlockSpaceManager`` on the window/full walk MiMo-V2
shares (``models/window_full.py``), against the plain reference
``benchmark/references/exaone_moe_decoder.py`` on LOGITS: the probe's after a
prefill, and the served tokens' after decoding through both stores for
several windows. A toy share (tests/benchmark/toy_exaone.py): layer 0 a window
layer with the dense MLP, a window of 16 shorter than a block, 4 of 16 experts
held, top 2; the norms, the selection bias and the router drawn as a trained
model's would be. Each of seven wrong models reads incorrect."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

from benchmark import cells, correctness, records, serving_app  # noqa: E402
from toy_exaone import learned_terms_at_one, toy_share  # noqa: E402
from toys import served_by  # noqa: E402

REFERENCE = "exaone_moe_decoder"
LIMITS = toy_share()["benchmark"]
WRONG = ("post_norm", "pre_norm", "rope_on_full", "qk_norm", "shared_expert", "selection_bias", "routed_scale")


def _serve(cfg, seed, slots=4, requests=5, new=40, prompts=None, buckets=(256,)):
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig
    from nxdi_tpu.serving.request import SamplingParams

    app = serving_app.build_app(cfg, list(buckets), seed=seed)
    app.load()
    learned_terms_at_one(app, seed)
    engine = InferenceEngine(app, SchedulerConfig(num_slots=slots))
    if prompts is None:
        served = served_by(engine, seed, requests=requests, new=new)
    else:
        reqs = [engine.add_request(p, SamplingParams(max_new_tokens=new, eos_token_ids=())) for p in prompts]
        outs = {}
        while engine.has_work():
            for o in engine.step():
                outs[o.request_id] = o
        served = [records.Served(i, 0.0, 0.0, len(p), new, r, outs[r.request_id], 1.0, prompt=p)
                  for i, (p, r) in enumerate(zip(prompts, reqs))]
    return SimpleNamespace(cfg=cfg, seed=seed, app=app, engine=engine, served=served)


def _judge(run, forward=None, tokens=100):
    forward = forward or cells.load_plugin("reference", REFERENCE)
    margins = cells.load_plugin("reference", REFERENCE, "routing_margins")
    if not hasattr(run, "got"):
        run.got = correctness.program_probe(run.app, correctness.probe_prompt(run.seed, 256), 256)
    samples = correctness.sample_served(run.served, run.seed, tokens=tokens)
    said = []
    out = correctness.check(run.app.params, run.cfg, forward, run.seed, run.got, samples, said.append,
                            routing_margins=margins)
    return out, said


@pytest.fixture(scope="module")
def share():
    return _serve(toy_share(), 12)


def test_prefill_and_decode_through_the_pool_and_the_ring_read_as_the_reference(share):
    window = share.cfg["sliding_window"]
    assert all(len(s.prompt) > window for s in share.served)
    assert all(len(s.output.token_ids) >= 2 * window for s in share.served)
    out, said = _judge(share)
    assert out["ok"], said
    # bf16 against float32: the program reads probe_mse <= 5e-6 and served_gap <= 0.005 on three
    # seeds (toy_exaone.py), the least wrong model 3.6e-4 and 0.10
    assert out["compared"]["probe_mse"]["value"] < LIMITS["logit_mse_tolerance"]
    assert out["compared"]["served_gap"]["value"] < LIMITS["served_gap_tolerance"]
    assert out["served_tokens"] >= 100 and out["reference_logit_std"] > 0.1


@pytest.mark.parametrize("term", WRONG)
def test_a_wrong_model_reads_incorrect(share, term):
    """The reference with one term dropped, misplaced or altered, in the
    program's place, judged at the toy's own limits: the program's own
    prefill and served tokens do not read as that model's."""
    without = cells.load_plugin("reference", REFERENCE, "forward_without")
    out, said = _judge(share, lambda p, c, ids: without(p, c, ids, term))
    assert not out["ok"], said
    assert out["compared"]["probe_mse"]["value"] > LIMITS["logit_mse_tolerance"], said
    assert out["compared"]["served_gap"]["value"] > LIMITS["served_gap_tolerance"], said


def test_an_unknown_term_is_refused(share):
    without = cells.load_plugin("reference", REFERENCE, "forward_without")
    with pytest.raises(ValueError, match="unknown terms"):
        without(share.app.params, share.cfg, [1, 2, 3], "no-such-term")


def test_the_tree_has_a_pool_and_a_ring_per_slot_and_both_strategies_are_recorded(share):
    cache = {k: v.shape for k, v in share.app._cache_struct().items()}
    # 1 full layer's pool (24 blocks of 128), 4 window layers' 16 ring rows for each of 4 slots
    assert cache == {"k": (1, 3072, 2, 16), "v": (1, 3072, 2, 16),
                     "k_swa": (4, 4, 2, 16, 16), "v_swa": (4, 4, 2, 16, 16)}
    arch = share.app.models["token_generation_model"].arch
    assert arch.schedule == (("swa", 0, 1, 0), ("swa", 1, 3, 1), ("full", 0, 1, 2), ("swa", 3, 4, 3))
    assert arch.routed == (False, True, True, True)
    assert arch.full.no_rope and not arch.swa.no_rope and arch.swa.sliding_window == 16
    assert arch.full.post_block_norm and arch.swa.qk_norm and arch.full.moe.routed_scaling == 2.5
    strategies = serving_app.program_strategies(share.app)
    assert set(strategies["token_generation_model[512]"]) == {"tkg_paged_kernel", "tkg_two_part_xla"}
    assert set(strategies["context_encoding_model[256]"]) == {"cte_flash_kernel"}
    assert serving_app.strategy_faults(share.app, share.cfg["benchmark"]["attention_strategies"]) == []


def test_a_fifth_request_reuses_a_slot_whose_ring_rows_another_left(share):
    seats = {}
    for rec in share.engine.flight.snapshot_records():
        for row in (rec.decode or {}).get("rows", []):
            seats.setdefault(row["slot"], set()).add(row["request_id"])
    assert len(seats) == 4 and any(len(ids) > 1 for ids in seats.values()), seats


def test_the_step_records_count_every_routed_layer_and_what_the_cache_holds(share):
    recs = [r for r in share.engine.flight.snapshot_records() if r.decode is not None]
    block = 1 * 128 * 2 * (16 + 16) * 2  # one layer, 128 tokens, 2 kv heads, bf16
    slot = 4 * 2 * 16 * (16 + 16) * 2  # four layers, 2 kv heads, 16 rows
    for r in recs:
        assert r.kv_window_rows_held == 16 * r.slots_busy
        assert r.kv_bytes_held == (24 - r.kv_blocks_free) * block + r.slots_busy * slot
    assert all(r.moe_routed_layers == 4 for r in recs)  # the dense layer 0 is no routed layer
    assert any(r.moe_held_pairs for r in recs)


def test_a_prefill_of_512_rows_is_compact_and_its_tail_lands_in_the_ring():
    """A 400-token prompt into the 512 bucket: the held experts take the compact
    form in every routed segment, the dense window segment's rows go into the
    ring with the routed ones', and what was served reads as the reference."""
    cfg = toy_share()
    cfg["benchmark"]["seq_len"] = 640
    prompt = np.random.default_rng(3).integers(0, 256, size=400).tolist()
    run = _serve(cfg, 12, prompts=[prompt], new=24, buckets=(256, 512))
    wrapper = run.app.models["context_encoding_model"]
    assert wrapper._programs[512].expert_forms == ("compact",)
    (prefill,) = [r for r in run.engine.flight.snapshot_records() if r.prefills]
    assert 0 < prefill.prefill_moe_held_pairs <= 4 * 2 * 400  # the real rows' pairs alone
    out, said = _judge(run, tokens=24)
    assert out["ok"], said


def test_a_preempted_request_is_prefilled_again_and_reads_as_the_reference():
    """Five blocks for four slots whose requests each grow from one block into
    a second: the pool runs out, a request loses its blocks and its slot, and
    is prefilled again later, its ring rows written anew."""
    cfg = toy_share()
    cfg["benchmark"]["pa_num_blocks"] = 5
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, size=int(n)).tolist() for n in (112, 120, 105, 126, 117, 109)]
    run = _serve(cfg, 2147483907, prompts=prompts)
    recs = run.engine.flight.snapshot_records()
    assert sum(len(r.preempted) for r in recs) > 0
    assert all(s.output is not None and len(s.output.token_ids) == 40 for s in run.served)
    out, said = _judge(run, tokens=240)
    assert out["ok"], said


def test_the_converter_maps_the_checkpoints_names_onto_the_served_tree():
    """An HF-named state dict of the toy: the post-attention norm lands on the
    ``input_layernorm`` key and the post-feed-forward norm on
    ``post_attention_layernorm`` (the post_block_norm keys), the share takes its
    own experts, and every leaf has the served tree's shape."""
    import jax

    from nxdi_tpu.models.registry import get_family

    cfg = toy_share()
    published = {k: v for k, v in cfg.items() if k not in serving_app.BENCHMARK_KEYS}
    family, cfg_cls = get_family("exaone_moe")
    config = cfg_cls(serving_app.tpu_config_of(cfg, [256], 16), load_config=lambda: dict(published))
    rng = np.random.default_rng(0)
    H, D, I, E = 64, 16, 32, 16
    sd = {"model.embed_tokens.weight": rng.standard_normal((256, H)), "model.norm.weight": np.ones(H),
          "lm_head.weight": rng.standard_normal((256, H))}
    for i in range(5):
        pre = f"model.layers.{i}."
        for name, shape in (("q_proj", (4 * D, H)), ("k_proj", (2 * D, H)), ("v_proj", (2 * D, H)),
                            ("o_proj", (H, 4 * D))):
            sd[pre + f"self_attn.{name}.weight"] = rng.standard_normal(shape)
        sd[pre + "self_attn.q_norm.weight"] = np.full(D, 2.0)
        sd[pre + "self_attn.k_norm.weight"] = np.full(D, 3.0)
        sd[pre + "post_attention_layernorm.weight"] = np.full(H, float(i + 10))
        sd[pre + "post_feedforward_layernorm.weight"] = np.full(H, float(i + 20))
        if i == 0:
            for p, shape in (("gate", (128, H)), ("up", (128, H)), ("down", (H, 128))):
                sd[pre + f"mlp.{p}_proj.weight"] = rng.standard_normal(shape)
            continue
        sd[pre + "mlp.gate.weight"] = rng.standard_normal((E, H))
        sd[pre + "mlp.e_score_correction_bias"] = rng.standard_normal(E)
        for j in range(E):
            for p, shape in (("gate", (I, H)), ("up", (I, H)), ("down", (H, I))):
                sd[pre + f"mlp.experts.{j}.{p}_proj.weight"] = np.full(shape, float(j))
        for p, shape in (("gate", (I, H)), ("up", (I, H)), ("down", (H, I))):
            sd[pre + f"mlp.shared_experts.{p}_proj.weight"] = rng.standard_normal(shape)
    params = family.convert_hf_state_dict(sd, config)
    struct = family.param_shape_struct(config)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(struct)
    for got, want in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(struct)):
        assert got.shape == want.shape and got.dtype == want.dtype
    window_routed = params["segments"][1]  # layers 1 and 2
    assert float(window_routed["input_layernorm"][1, 0]) == 12.0  # layer 2's post-attention norm
    assert float(window_routed["post_attention_layernorm"][0, 0]) == 21.0  # layer 1's post-ffn norm
    assert float(window_routed["attn"]["k_norm"][0, 0]) == 3.0
    # experts 4..7 held: the share converts its own alone
    assert [float(window_routed["moe"]["experts"]["up_proj"]["w"][0, j, 0, 0]) for j in range(4)] == [4, 5, 6, 7]
    assert window_routed["moe"]["router"]["w"].shape == (2, H, E)


@pytest.mark.parametrize("kwargs, named", [
    (dict(is_prefix_caching=True), "prefix/chunked prefill"),
    (dict(chunked_prefill_config={}), "prefix/chunked prefill"),
    (dict(role="prefill"), "hand-off"),
    (dict(mixed_dispatch=True), "mixed dispatch"),
], ids=["prefix-caching", "chunked-prefill", "hand-off", "mixed-dispatch"])
def test_what_the_app_cannot_do_is_refused_by_name(kwargs, named):
    from nxdi_tpu.models.registry import get_family

    cfg = toy_share()
    cfg["benchmark"]["tpu_config"] = kwargs
    published = {k: v for k, v in cfg.items() if k not in serving_app.BENCHMARK_KEYS}
    family, cfg_cls = get_family(cfg["model_type"])
    with pytest.raises((NotImplementedError, ValueError), match=named):
        config = cfg_cls(serving_app.tpu_config_of(cfg, [256], 16), load_config=lambda: dict(published))
        serving_app.application_class(family)("<shapes>", config, model_family=family)


def test_the_contiguous_layout_is_refused_by_name():
    from nxdi_tpu.config import TpuConfig
    from nxdi_tpu.models.registry import get_family

    cfg = toy_share()
    published = {k: v for k, v in cfg.items() if k not in serving_app.BENCHMARK_KEYS}
    family, cfg_cls = get_family(cfg["model_type"])
    config = cfg_cls(TpuConfig(tp_degree=1, batch_size=2, seq_len=256), load_config=lambda: dict(published))
    with pytest.raises(NotImplementedError, match="exaone_moe does not support a cache that is not the block KV"):
        serving_app.application_class(family)("<shapes>", config, model_family=family)


@pytest.mark.parametrize("change, named", [
    (dict(sliding_windows=[16, 16, 8, 0, 16, 16, 16, 0]), "one window"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}), "plain RoPE"),
    (dict(layer_types=["sliding_attention"] * 3), "3 entries for 5 layers"),
], ids=["two-windows", "rope-scaling", "short-list"])
def test_a_config_the_family_cannot_read_is_refused(change, named):
    from nxdi_tpu.models.registry import get_family

    cfg = toy_share(**change)
    published = {k: v for k, v in cfg.items() if k not in serving_app.BENCHMARK_KEYS}
    _, cfg_cls = get_family(cfg["model_type"])
    with pytest.raises((NotImplementedError, ValueError), match=named):
        cfg_cls(serving_app.tpu_config_of(cfg, [256], 16), load_config=lambda: dict(published))


@pytest.mark.parametrize("toy", ["toy_exaone", "toy_mimo"])
def test_the_ring_commit_lies_under_the_window_layers_scope_in_both_hybrids(toy):
    """The token-generation program of each window/full family: the ring rows'
    commit after the walk (``kv.write``) lies under the window layers'
    container ``layers.window``, so a reader of that container counts it with
    the rest of their attention, and no ``kv.write`` is left at the top."""
    import importlib
    import re

    import jax

    from nxdi_tpu.parallel.mesh import mesh_from_config

    cfg = importlib.import_module(toy).toy_share()
    app = serving_app.build_app(cfg, [256], seed=0)
    app.mesh = mesh_from_config(app.tpu_config, devices=jax.devices()[:1])
    app._build_wrappers()
    (compiled,) = app.models["token_generation_model"].aot_compile(
        app.build_params_struct(), app._cache_struct()).values()
    ops = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    writes = [op.split("/") for op in ops if "kv.write" in op.split("/")]
    assert any(p[1:3] == ["layers.window", "kv.write"] for p in writes), sorted(set(ops))[:40]
    assert not any(p[1] == "kv.write" for p in writes)  # none outside a kind's container
