"""MiniCPM-SALA on the paged path (PR 37): the sparse layers' rows in the block
pool, their index of compressed keys and the lightning layers' float32 state a
slot beside it, through ``InferenceEngine`` and ``BlockSpaceManager``, against
the plain reference ``benchmark/references/sparse_linear_decoder.py`` on LOGITS:
after a prefill past ``dense_len``, and after decoding through pool, index and
state across block boundaries and index windows. A toy ``sparse_config``
(dense_len 64, block 8, kernel 4 / stride 2, topk 6, window 16) at the published
ratio of kinds; the mixers' projections are drawn at 1 / sqrt(fan in)."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

from benchmark import cells, records, serving_app  # noqa: E402
from toy_sala import MIXERS, learned_terms_at_one, toy_stage  # noqa: E402

REFERENCE = "sparse_linear_decoder"
VOCAB = 256
#: a decided served position's gap (the toy's own limit: bf16's noise reads <= 0.0065, a wrong
#: block, index row, position or state 0.015 and more: tests/benchmark/toy_sala.py)
LIMIT = toy_stage()["benchmark"]["served_gap_tolerance"]


def _engine(cfg, seed, slots=4, buckets=(128,), **scheduler):
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    app = serving_app.build_app(cfg, list(buckets), seed=seed)
    app.load()
    learned_terms_at_one(app, seed)
    return app, InferenceEngine(app, SchedulerConfig(num_slots=slots, **scheduler))


def _serve(engine, prompts, new):
    from nxdi_tpu.serving.request import SamplingParams

    reqs = [engine.add_request(p, SamplingParams(max_new_tokens=new, eos_token_ids=())) for p in prompts]
    outs = {}
    while engine.has_work():
        for o in engine.step():
            outs[o.request_id] = o
    return [records.Served(i, 0.0, 0.0, len(p), new, r, outs[r.request_id], 1.0, prompt=p)
            for i, (p, r) in enumerate(zip(prompts, reqs))]


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=int(n)).tolist() for n in lengths]


def _reference_logits(app, cfg, ids):
    forward = cells.load_plugin("reference", REFERENCE)
    return np.asarray(forward(app.params, cfg, np.asarray(ids, np.int32))[:, :VOCAB])


@pytest.fixture(scope="module")
def stage():
    cfg = toy_stage()
    app, engine = _engine(cfg, 12)
    # prompts past dense_len (64) and under it; 48 new tokens cross six block
    # boundaries (8) and 24 index windows (stride 2)
    prompts = _prompts(12, (100, 40, 70, 120, 90))
    served = _serve(engine, prompts, 48)
    return SimpleNamespace(cfg=cfg, app=app, engine=engine, served=served, seed=12)


def _gaps(run, served):
    """At every served position: the reference's top logit less its logit of the
    served token (0 where the program's greedy choice is the reference's). A
    position where the reference's own selection nearly ties (its margin under
    the toy's ``routing_margin``: one block swapped is rounding, not a fault) is
    held to what one swapped block moves, every other to bf16's own noise."""
    margins_of = cells.load_plugin("reference", REFERENCE, "routing_margins")
    b = run.cfg["benchmark"]
    out = []
    for s in served:
        tokens = np.asarray(s.output.token_ids)
        ids = np.concatenate([np.asarray(s.prompt), tokens[:-1]])
        rows = _reference_logits(run.app, run.cfg, ids)[len(s.prompt) - 1:]
        gap = rows.max(axis=-1) - rows[np.arange(len(tokens)), tokens]
        tied = np.asarray(margins_of(run.app.params, run.cfg, ids))[len(s.prompt) - 1:] < b["routing_margin"]
        assert gap[tied].max(initial=0.0) < b["logit_tolerance_undecided"], gap[tied]
        out.append(gap[~tied])
    return out


def test_the_probe_reads_the_reference_logits_after_a_prefill_past_dense_len(stage):
    from benchmark import correctness

    prompt = _prompts(5, (128,))[0]  # the whole bucket: 64 dense positions, 64 that select
    from nxdi_tpu.utils.accuracy import probe_all_logits

    correctness.lend_pool_to_probe(stage.app)
    try:
        got = probe_all_logits(stage.app, np.asarray(prompt)[None, :])[0][:, :VOCAB].astype(np.float32)
    finally:
        correctness.take_pool_back(stage.app)
    ref = _reference_logits(stage.app, stage.cfg, prompt)
    assert ref.std() > 0.03
    # bf16 against float32: every position, the dense ones and the selecting ones alike
    assert np.abs(got - ref).max() < 0.02, np.abs(got - ref).max(axis=-1)
    assert ((got - ref) ** 2).mean() < 4e-6


def test_decoding_through_pool_index_and_state_reads_as_the_reference(stage):
    gaps = _gaps(stage, stage.served)
    assert sum(len(g) for g in gaps) > 60  # the decided positions of 5 x 48 (a toy of 6 blocks ties often)
    # a wrong block, index row, position or state reads ~ the logit std (0.04) here
    assert max(g.max() for g in gaps) < LIMIT, [float(g.max()) for g in gaps]
    assert np.mean([np.mean(g == 0) for g in gaps]) > 0.9  # nearly always the reference's own token


def test_a_prompt_under_dense_len_crosses_it_while_decoding(stage):
    short = stage.served[1]
    assert len(short.prompt) == 40 and len(short.prompt) + 48 > stage.cfg["sparse_config"]["dense_len"]
    (gap,) = _gaps(stage, [short])
    assert gap.max() < LIMIT
    # the counters say when its rows began to select: live blocks grow, read blocks stop at topk
    recs = [r for r in stage.engine.flight.snapshot_records() if r.decode is not None]
    assert all(r.sparse_blocks_read <= r.sparse_blocks_live for r in recs)
    assert any(r.sparse_blocks_read < r.sparse_blocks_live for r in recs)


def test_a_fifth_request_reuses_a_slot_another_left(stage):
    seats = {}
    for rec in stage.engine.flight.snapshot_records():
        for row in (rec.decode or {}).get("rows", []):
            seats.setdefault(row["slot"], set()).add(row["request_id"])
    assert len(seats) == 4 and any(len(ids) > 1 for ids in seats.values()), seats
    (gap,) = _gaps(stage, [stage.served[4]])  # the request that took over a slot's index and state
    assert gap.max() < LIMIT


def test_the_tree_has_a_pool_an_index_and_a_state_and_both_strategies_are_recorded(stage):
    cache = {k: (v.shape, str(v.dtype)) for k, v in stage.app._cache_struct().items()}
    # 2 sparse layers' pool (160 blocks of 8), their index (127 windows in 256 tokens, whole tiles
    # of rows) and 2 lightning layers' state for 4 slots and a spare one
    assert cache == {"k": ((2, 1280, 2, 16), "bfloat16"), "v": ((2, 1280, 2, 16), "bfloat16"),
                     "kc": ((2, 4, 128, 2, 16), "bfloat16"), "lin_state": ((2, 5, 4, 16, 16), "float32")}
    strategies = serving_app.program_strategies(stage.app)
    assert set(strategies["token_generation_model[256]"]) == {"tkg_paged_kernel"}
    assert set(strategies["context_encoding_model[128]"]) == {"cte_flash_kernel"}
    assert serving_app.strategy_faults(stage.app, stage.cfg["benchmark"]["attention_strategies"]) == []
    held = [r for r in stage.engine.flight.snapshot_records() if r.kv_bytes_held]
    assert held and all(r.kv_window_rows_held % 128 == 0 for r in held)  # index rows a busy slot


def test_a_preempted_request_rebuilds_its_index_and_state_in_its_prefill():
    cfg = toy_stage()
    app, engine = _engine(cfg, 7)
    from nxdi_tpu.serving.request import SamplingParams

    prompts = _prompts(7, (90, 75))
    reqs = [engine.add_request(p, SamplingParams(max_new_tokens=30, eos_token_ids=())) for p in prompts]
    outs, steps = {}, 0
    while engine.has_work():
        for o in engine.step():
            outs[o.request_id] = o
        steps += 1
        if steps == 12:
            assert engine.preempt_youngest() is not None
    served = [records.Served(i, 0.0, 0.0, len(p), 30, r, outs[r.request_id], 1.0, prompt=p)
              for i, (p, r) in enumerate(zip(prompts, reqs))]
    assert sum(r.preemptions for r in reqs) == 1
    run = SimpleNamespace(app=app, cfg=cfg)
    assert max(g.max() for g in _gaps(run, served)) < LIMIT


def test_chained_and_unchained_order_give_the_same_tokens():
    cfg = toy_stage()
    prompts = _prompts(3, (100, 66, 30))
    tokens = {}
    for chained in (True, False):
        app, engine = _engine(cfg, 3)
        if not chained:  # a post hook holds the engine to the synchronous order
            app.models["token_generation_model"].post_hooks.append(lambda *a, **k: None)
        served = _serve(engine, prompts, 24)
        recs = [r for r in engine.flight.snapshot_records() if r.decode is not None]
        assert any(r.chained for r in recs) == chained
        tokens[chained] = [list(s.output.token_ids) for s in served]
    assert tokens[True] == tokens[False]


def test_the_chunked_prefill_of_a_lightning_layer_is_the_recurrence():
    import jax.numpy as jnp

    from nxdi_tpu.ops import linear_attention as la

    rng = np.random.default_rng(0)
    B, S, H, D = 2, 96, 4, 16
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32) for _ in range(3))
    rates = la.decay_rates(H, 3, 8)
    last = jnp.asarray([S - 1, 40])
    o_ref, state_ref = la.recurrence(q, k, v, rates)
    _, state_40 = la.recurrence(q[:, :41], k[:, :41], v[:, :41], rates)
    for chunk in (96, 32, 16):
        o, state = la.chunked_prefill(q, k, v, rates, last, chunk=chunk)
        assert float(jnp.abs(o - o_ref).max()) < 2e-4, chunk
        assert float(jnp.abs(state[0] - state_ref[0]).max()) < 2e-4  # at the row's last real token
        assert float(jnp.abs(state[1] - state_40[1]).max()) < 2e-4  # padding behind it never lands
    # one decode step on the slot's state, in place, kernel and XLA alike
    store = la.write_states(jnp.zeros((3, 5, H, D, D), jnp.float32), 1, jnp.asarray([2, 4]), state)
    q1, k1, v1 = (jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32) for _ in range(3))
    o_x, store_x = la.decode_step(store, 1, jnp.asarray([2, 4]), q1, k1, v1, rates, use_kernel=False)
    o_k, store_k = la.decode_step(store, 1, jnp.asarray([2, 4]), q1, k1, v1, rates, use_kernel=True)
    o_r, state_r = la.recurrence(q1[:, None], k1[:, None], v1[:, None], rates, state=state)
    assert float(jnp.abs(o_x - o_r[:, 0]).max()) < 1e-4 and float(jnp.abs(o_k - o_x).max()) < 1e-4
    assert float(jnp.abs(store_k - store_x).max()) < 1e-5
    assert float(jnp.abs(store_x[1, jnp.asarray([2, 4])] - state_r).max()) < 1e-4
    assert float(jnp.abs(store_x[0]).max()) == 0.0 and float(jnp.abs(store_x[1, 0]).max()) == 0.0


def test_the_cut_is_tied_to_the_model():
    """A toy 8-layer model's layers 4..7, given the uncut reference's hidden state
    after layer 3, reproduce its state after layer 7: with the residual
    multiplier and the decay's layer factor at the UNCUT depth."""
    import jax

    reference = cells.load_plugin("reference", REFERENCE, "hidden_states")
    whole = toy_stage(num_hidden_layers=8, first_hidden_layer=0)
    cut = toy_stage()
    assert cut["first_hidden_layer"] == 4 and cut["num_hidden_layers_total"] == 8 and len(MIXERS) == 8
    app = serving_app.build_app(whole, [128], seed=9)
    drawn = serving_app.seeded_params(app.build_params_struct(), None, 9)
    params = learned_terms_at_one(SimpleNamespace(params=drawn), 9).params
    # the whole model's segments are [S][LLL][S][LL][S]; the cut holds the last four layers
    segs = params["segments"]
    assert [jax.tree_util.tree_leaves(s)[0].shape[0] for s in segs] == [1, 3, 1, 2, 1]
    cut_params = dict(params, segments=segs[2:])
    ids = _prompts(9, (96,))[0]
    states, _ = reference(params, whole, ids, collect=True)
    got, _ = reference(cut_params, cut, ids, first_hidden=np.asarray(states[3])[:96])
    assert np.abs(np.asarray(got)[:96] - np.asarray(states[7])[:96]).max() < 1e-4
    # and NOT with the cut's own depth in their place
    shallow = dict(cut, num_hidden_layers_total=4, first_hidden_layer=0, mixer_types=MIXERS[4:])
    wrong, _ = reference(cut_params, shallow, ids, first_hidden=np.asarray(states[3])[:96])
    assert np.abs(np.asarray(wrong)[:96] - np.asarray(states[7])[:96]).max() > 1e-2


def test_minicpm4_with_a_sparse_config_is_refused_by_name():
    from toys import toy_config

    from nxdi_tpu.models.registry import get_family

    cfg = toy_config("minicpm4", sparse_config={"dense_len": 8192, "topk": 64})
    family, cfg_cls = get_family("minicpm4")
    published = {k: v for k, v in cfg.items() if k not in serving_app.BENCHMARK_KEYS}
    with pytest.raises(NotImplementedError, match="sparse_config.*dense_len 8192"):
        cfg_cls(serving_app.tpu_config_of(cfg, [128], 16), load_config=lambda: dict(published))
    published.pop("sparse_config")  # without the key the family serves the model dense, as ever
    cfg_cls(serving_app.tpu_config_of(cfg, [128], 16), load_config=lambda: dict(published))


@pytest.mark.parametrize("flag, why", [
    (dict(is_prefix_caching=True), "prefix caching"),
    (dict(mixed_dispatch=True), "mixed dispatch"),
    (dict(kv_quant_config={"dtype": "float8_e4m3fn"}), "quantized cache"),
])
def test_the_application_refuses_by_name_what_it_cannot_serve(flag, why):
    cfg = toy_stage()
    cfg["benchmark"]["tpu_config"] = flag
    with pytest.raises(NotImplementedError, match=why):
        serving_app.build_app(cfg, [128], seed=1)
