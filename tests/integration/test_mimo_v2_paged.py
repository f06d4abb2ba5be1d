"""MiMo-V2 on the paged path (PR 35): the full layers' rows in the block pool,
the window layers' in ring rows a slot beside it, through ``InferenceEngine``
and ``BlockSpaceManager``, against the plain reference
``benchmark/references/window_moe_decoder.py``: the probe's logits after a
prefill, and the served tokens after decoding through both stores for more
than twice the window. Sinks and the router's selection bias are drawn at 1.0;
every prompt is longer than the window."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

from benchmark import cells, correctness, serving_app  # noqa: E402
from toy_mimo import learned_terms_at_one, toy_share  # noqa: E402
from toys import served_by  # noqa: E402

REFERENCE = "window_moe_decoder"
CASES = {
    "share": {},  # experts 4..7 of 16
    "whole": dict(n_routed_experts=16, first_routed_expert=0),
    # a key row of two lane tiles (160 -> 2 x 128) in the pool, values 128 wide
    "wide-keys": dict(head_dim=160, v_head_dim=128, swa_head_dim=160, swa_v_head_dim=128),
}


def _served_prompts(engine, prompts, new):
    """``toys.served_by`` with the prompts given."""
    from benchmark import records
    from nxdi_tpu.serving.request import SamplingParams

    reqs = [engine.add_request(p, SamplingParams(max_new_tokens=new, eos_token_ids=())) for p in prompts]
    outs = {}
    while engine.has_work():
        for o in engine.step():
            outs[o.request_id] = o
    return [records.Served(i, 0.0, 0.0, len(p), new, r, outs[r.request_id], 1.0, prompt=p)
            for i, (p, r) in enumerate(zip(prompts, reqs))]


def _serve(cfg, seed, slots=4, requests=5, new=40, before=None, prompts=None):
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    app = serving_app.build_app(cfg, [256], seed=seed)
    app.load()
    learned_terms_at_one(app, seed)
    if before is not None:
        before(app)
    engine = InferenceEngine(app, SchedulerConfig(num_slots=slots))
    served = (served_by(engine, seed, requests=requests, new=new) if prompts is None
              else _served_prompts(engine, prompts, new))
    return SimpleNamespace(cfg=cfg, seed=seed, app=app, engine=engine, served=served)


def _judge(run, tokens=100):
    forward = cells.load_plugin("reference", REFERENCE)
    margins = cells.load_plugin("reference", REFERENCE, "routing_margins")
    got = correctness.program_probe(run.app, correctness.probe_prompt(run.seed, 256), 256)
    samples = correctness.sample_served(run.served, run.seed, tokens=tokens)
    said = []
    out = correctness.check(run.app.params, run.cfg, forward, run.seed, got, samples, said.append,
                            routing_margins=margins)
    return out, said


@pytest.fixture(scope="module")
def share():
    return _serve(toy_share(), 12)


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_and_decode_through_both_stores_read_as_the_reference(case, share):
    run = share if case == "share" else _serve(toy_share(**CASES[case]), 7)
    assert all(len(s.prompt) > run.cfg["sliding_window"] for s in run.served)
    assert all(len(s.output.token_ids) >= 2 * run.cfg["sliding_window"] for s in run.served)
    out, said = _judge(run)
    assert out["ok"], said
    assert out["compared"]["probe_mse"]["value"] < 4e-6  # the prefill's logits, every position
    assert out["compared"]["served_gap"]["value"] < 0.02  # 40 decode steps over a ring of 16 rows


def test_the_tree_has_a_pool_and_a_store_per_slot_and_both_strategies_are_recorded(share):
    cache = {k: v.shape for k, v in share.app._cache_struct().items()}
    # 2 full layers' pool (24 blocks of 128) and 4 window layers' 16 ring rows for each of 4 slots
    assert cache == {"k": (2, 3072, 2, 24), "v": (2, 3072, 2, 16),
                     "k_swa": (4, 4, 4, 16, 24), "v_swa": (4, 4, 4, 16, 16)}
    wide = serving_app.build_app(toy_share(**CASES["wide-keys"]), [256], seed=1)._cache_struct()
    assert wide["k"].shape == (2 * 2, 3072, 2, 128) and wide["v"].shape == (2, 3072, 2, 128)
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


def test_the_step_records_say_what_the_cache_holds(share):
    recs = [r for r in share.engine.flight.snapshot_records() if r.decode is not None]
    block = (2 * 128 * 2 * (24 + 16)) * 2  # two layers, 128 tokens, 2 kv heads, bf16
    slot = 4 * 4 * 16 * (24 + 16) * 2  # four layers, 4 kv heads, 16 rows
    for r in recs:
        assert r.kv_window_rows_held == 16 * r.slots_busy
        assert r.kv_live_tokens > 0
        assert r.kv_bytes_held == (24 - r.kv_blocks_free) * block + r.slots_busy * slot
    assert recs[0].to_dict()["kv_live_tokens"] == recs[0].kv_live_tokens
    assert "nxdi_kv_window_rows_held" in share.app.telemetry.prometheus_text()
    assert all(r.moe_routed_layers == 5 for r in recs)  # every routed layer of both kinds counted
    run = SimpleNamespace(steps=recs)
    assert cells.load_plugin("per_layer", "kv.bytes_per_live_token")(run) == pytest.approx(
        float(np.median([r.kv_bytes_held / r.kv_live_tokens for r in recs])))


def test_a_preempted_request_is_prefilled_again_and_reads_as_the_reference():
    """Five blocks for four slots whose requests each grow from one block into
    a second: the pool runs out, a request loses its blocks and its slot, and
    is prefilled again later, its ring rows written anew into whatever slot is
    free then."""
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


def test_chained_and_collected_first_orders_serve_the_same_tokens(share):
    from nxdi_tpu.runtime.application import TAG_TOKEN_GENERATION

    def collect_first(app):  # a post hook reads every dispatch on the host: no flight stays open
        app.models[TAG_TOKEN_GENERATION].post_hooks.append(lambda tag: None)

    held = _serve(toy_share(), 12, before=collect_first)
    assert any(r.chained for r in share.engine.flight.snapshot_records())
    assert not any(r.chained for r in held.engine.flight.snapshot_records())
    assert [s.output.token_ids for s in held.served] == [s.output.token_ids for s in share.served]


def test_the_shares_partial_sums_add_up_to_the_whole_layer():
    """Four chips of four experts each: what their routed layers return, each
    with nothing standing in for the others, sums to the uncut layer's."""
    import importlib.util

    import jax.numpy as jnp

    path = os.path.join(ROOT, "benchmark", "references", f"{REFERENCE}.py")
    spec = importlib.util.spec_from_file_location("window_moe_decoder_under_test", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    rng = np.random.default_rng(3)
    H, E, I, S = 64, 16, 32, 48
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3, jnp.float32)  # noqa: E731
    seg = {"post_attention_layernorm": jnp.ones((1, H)),
           "moe": {"router": {"w": draw(1, H, E), "e_bias": draw(1, E)},
                   "experts": {"gate_proj": {"w": draw(1, E, H, I)}, "up_proj": {"w": draw(1, E, H, I)},
                               "down_proj": {"w": draw(1, E, I, H)}}}}
    x = draw(S, H)

    def routed(first, held):
        cfg = toy_share(n_routed_experts=held, first_routed_expert=first)
        part = {**seg, "moe": {**seg["moe"], "experts": {
            k: {"w": v["w"][:, first:first + held]} for k, v in seg["moe"]["experts"].items()}}}
        return ref._build(cfg, frozenset())["routed_ffn"](x, part, jnp.int32(0))[0] - x

    whole = routed(0, 16)
    shares = sum(routed(first, 4) for first in (0, 4, 8, 12))
    assert float(jnp.abs(whole).max()) > 0.05
    np.testing.assert_allclose(np.asarray(shares), np.asarray(whole), atol=2e-5)


@pytest.mark.parametrize("kwargs, named", [
    (dict(is_prefix_caching=True), "prefix/chunked prefill"),
    (dict(chunked_prefill_config={}), "prefix/chunked prefill"),
    (dict(role="prefill"), "hand-off"),
], ids=["prefix-caching", "chunked-prefill", "hand-off"])
def test_what_the_paged_app_cannot_do_is_refused_by_name(kwargs, named):
    from nxdi_tpu.models.registry import get_family

    cfg = toy_share()
    cfg["benchmark"]["tpu_config"] = kwargs
    published = {k: v for k, v in cfg.items() if k not in serving_app.BENCHMARK_KEYS}
    family, cfg_cls = get_family(cfg["model_type"])
    with pytest.raises((NotImplementedError, ValueError), match=named):
        config = cfg_cls(serving_app.tpu_config_of(cfg, [256], 16), load_config=lambda: dict(published))
        serving_app.application_class(family)("<shapes>", config, model_family=family)


def test_a_user_set_ring_under_the_block_layout_is_refused_with_what_to_do():
    from nxdi_tpu.config import TpuConfig

    with pytest.raises(ValueError, match="user-set ring of the contiguous layout.*leave it off"):
        TpuConfig(tp_degree=1, batch_size=2, seq_len=256, is_block_kv_layout=True, pa_block_size=128,
                  pa_num_blocks=8, window_sized_kv=True, sliding_window=16)
    with pytest.raises(ValueError, match="medusa/prefix modes"):
        TpuConfig(tp_degree=1, batch_size=2, seq_len=256, window_sized_kv=True, sliding_window=16,
                  is_prefix_caching=True, is_block_kv_layout=False)
