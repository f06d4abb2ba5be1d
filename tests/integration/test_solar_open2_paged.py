"""Solar-Open2 on the paged path: the GQA layer's rows in the block pool, the KDA
layers' float32 state and convolution window a slot beside it, through
``InferenceEngine`` and ``BlockSpaceManager``, against the plain reference
``benchmark/references/kda_moe_decoder.py`` on LOGITS: after a prefill, and after
decoding through the pool and both stores. A toy share (tests/benchmark/
toy_solar.py): 8 of 64 experts held, top 2, so the 4-row decode runs the sorted
form and the 128-row prefill the dense one; the mixers' projections and decays
drawn as a trained model's would be (long memory, O(1) keys and gates)."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

from benchmark import cells, records, serving_app  # noqa: E402
from toy_solar import learned_terms_at_one, toy_share  # noqa: E402

REFERENCE = "kda_moe_decoder"
VOCAB = 256
LIMITS = toy_share()["benchmark"]
#: a decided served position's gap (the toy's own limit: bf16's noise reads <= 0.029, a wrong
#: term 0.23 and more: tests/benchmark/toy_solar.py)
LIMIT = LIMITS["served_gap_tolerance"]


def _engine(cfg, seed, slots=4, buckets=(128,)):
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    app = serving_app.build_app(cfg, list(buckets), seed=seed)
    app.load()
    learned_terms_at_one(app, seed)
    return app, InferenceEngine(app, SchedulerConfig(num_slots=slots))


def _serve(engine, prompts, new, preempt_at=None):
    from nxdi_tpu.serving.request import SamplingParams

    reqs = [engine.add_request(p, SamplingParams(max_new_tokens=new, eos_token_ids=())) for p in prompts]
    outs, steps = {}, 0
    while engine.has_work():
        for o in engine.step():
            outs[o.request_id] = o
        steps += 1
        if steps == preempt_at:
            assert engine.preempt_youngest() is not None
    return [records.Served(i, 0.0, 0.0, len(p), new, r, outs[r.request_id], 1.0, prompt=p)
            for i, (p, r) in enumerate(zip(prompts, reqs))]


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=int(n)).tolist() for n in lengths]


def _reference_logits(app, cfg, ids):
    forward = cells.load_plugin("reference", REFERENCE)
    return np.asarray(forward(app.params, cfg, np.asarray(ids, np.int32))[:, :VOCAB])


def _gaps(run, served):
    """At every served position: the reference's top logit less its logit of the
    served token. A position whose margin (a router's near tie, a KDA head
    normed from a direction rounding can turn) lies under the toy's
    ``routing_margin`` is held to the undecided limit, every other to bf16's
    own noise."""
    margins_of = cells.load_plugin("reference", REFERENCE, "routing_margins")
    out = []
    for s in served:
        tokens = np.asarray(s.output.token_ids)
        ids = np.concatenate([np.asarray(s.prompt), tokens[:-1]])
        rows = _reference_logits(run.app, run.cfg, ids)[len(s.prompt) - 1:]
        gap = rows.max(axis=-1) - rows[np.arange(len(tokens)), tokens]
        tied = np.asarray(margins_of(run.app.params, run.cfg, ids))[len(s.prompt) - 1:] < LIMITS["routing_margin"]
        assert gap[tied].max(initial=0.0) < LIMITS["logit_tolerance_undecided"], gap[tied]
        out.append(gap[~tied])
    return out


@pytest.fixture(scope="module")
def share():
    cfg = toy_share()
    app, engine = _engine(cfg, 12)
    # prompts on and off the prefill's chunk of 16; 40 new tokens each, five requests on four slots
    served = _serve(engine, _prompts(12, (100, 40, 70, 128, 90)), 40)
    return SimpleNamespace(cfg=cfg, app=app, engine=engine, served=served)


def test_the_probe_reads_the_reference_logits_after_a_prefill(share):
    from benchmark import correctness
    from nxdi_tpu.utils.accuracy import probe_all_logits

    prompt = _prompts(5, (128,))[0]
    correctness.lend_pool_to_probe(share.app)
    try:
        got = probe_all_logits(share.app, np.asarray(prompt)[None, :])[0][:, :VOCAB].astype(np.float32)
    finally:
        correctness.take_pool_back(share.app)
    ref = _reference_logits(share.app, share.cfg, prompt)
    assert ref.std() > 0.1
    assert np.abs(got - ref).max() < LIMITS["logit_tolerance"], np.abs(got - ref).max(axis=-1)
    assert ((got - ref) ** 2).mean() < LIMITS["logit_mse_tolerance"]


def test_decoding_through_the_pool_and_both_stores_reads_as_the_reference(share):
    gaps = _gaps(share, share.served)
    assert sum(len(g) for g in gaps) > 150  # the decided positions of 5 x 40
    assert max(g.max() for g in gaps) < LIMIT, [float(g.max()) for g in gaps]
    assert np.mean([np.mean(g == 0) for g in gaps]) > 0.9  # nearly always the reference's own token


def test_a_fifth_request_reuses_a_slot_whose_state_and_window_another_left(share):
    seats = {}
    for rec in share.engine.flight.snapshot_records():
        for row in (rec.decode or {}).get("rows", []):
            seats.setdefault(row["slot"], set()).add(row["request_id"])
    assert len(seats) == 4 and any(len(ids) > 1 for ids in seats.values()), seats
    (gap,) = _gaps(share, [share.served[4]])
    assert gap.max() < LIMIT


def test_padding_rows_land_in_the_spare_slot(share):
    """A decode batch of fewer live rows than slots pads with repeats of a live
    row's slot; the in-place update is not idempotent, so the repeats go to the
    stores' last slot and the live rows' states are updated once."""
    import jax.numpy as jnp

    from nxdi_tpu.ops.linear_attention import spare_slots

    assert spare_slots(jnp.asarray([2, 0, 2, 2]), 5).tolist() == [2, 0, 4, 4]
    assert spare_slots(jnp.asarray([3, 1, 0, 2]), 5).tolist() == [3, 1, 0, 2]
    rows = [len((r.decode or {}).get("rows", [])) for r in share.engine.flight.snapshot_records()]
    assert 0 < min(r for r in rows if r) < 4  # the last requests decoded with padding rows
    cache = share.app.kv_cache
    for name in ("kda_state", "kda_conv"):
        assert cache[name].shape[1] == 5  # four slots and the spare one
        assert float(jnp.abs(cache[name][:, 4].astype(jnp.float32)).max()) > 0, name  # what the repeats wrote


def test_the_tree_has_a_pool_and_two_stores_and_the_programs_are_recorded(share):
    cache = {k: (v.shape, str(v.dtype)) for k, v in share.app._cache_struct().items()}
    # 1 GQA layer's pool (96 blocks of 16); 3 KDA layers' windows (3 rows of q, k and v, 4 heads of 16)
    # and float32 states, 4 slots and a spare one
    assert cache == {"k": ((1, 1536, 2, 16), "bfloat16"), "v": ((1, 1536, 2, 16), "bfloat16"),
                     "kda_conv": ((3, 5, 3, 3, 64), "bfloat16"), "kda_state": ((3, 5, 4, 16, 16), "float32")}
    strategies = serving_app.program_strategies(share.app)
    assert set(strategies["token_generation_model[256]"]) == {"tkg_paged_kernel"}
    assert set(strategies["context_encoding_model[128]"]) == {"cte_flash_kernel"}
    assert serving_app.strategy_faults(share.app, share.cfg["benchmark"]["attention_strategies"]) == []
    forms = {prog.label: prog.expert_forms for w in share.app.models.values() for prog in w._programs.values()}
    # token generation: 4 rows x top 2 < 64, a held expert expects less than a row (rule (a));
    # the 128-row prefill streams every held expert anyway and is under twice the ridge: dense
    assert forms == {"token_generation_model[256]": ("sorted",), "context_encoding_model[128]": ("dense",)}
    recs = [r for r in share.engine.flight.snapshot_records() if r.decode is not None]
    # bytes held count the pool's blocks and both stores of every seated slot
    slot_bytes = 3 * 5 * 3 * 3 * 64 * 2 // 5 + 3 * 5 * 4 * 16 * 16 * 4 // 5
    assert all(r.kv_bytes_held >= slot_bytes * len(r.decode["rows"]) for r in recs if r.kv_bytes_held)
    assert any(r.moe_held_pairs for r in recs)


def test_a_compact_prefill_reads_as_the_reference_and_returns_its_pairs_and_slot_rows():
    """A 400-token prompt in the 512 bucket: 512 rows are twice the slots a
    held expert gets (the ridge of the chip the share was built for, in whole
    tiles), so the four routed layers (the GQA layer's scan and the KDA scan)
    take the compact form; the program returns the live pairs and the slot
    rows of both scans beside its token, the engine puts them on the step's
    record, and what it serves reads as the reference."""
    from nxdi_tpu.ops.moe import compact_capacity

    cfg = toy_share()
    cfg["benchmark"]["seq_len"] = 1024
    app, engine = _engine(cfg, 14, slots=2, buckets=(512,))
    served = _serve(engine, _prompts(14, (400,)), 8)
    (cte,) = app.models["context_encoding_model"]._programs.values()
    assert cte.expert_forms == ("compact",)
    (prefill,) = [r for r in engine.flight.snapshot_records() if r.prefills]
    slots = 8 * compact_capacity(app.models["context_encoding_model"].arch.gqa.moe, 512)
    # every routed layer runs a trip at least (a held expert expects 400 x 2 / 64 = 12.5 rows); the pairs
    # are the real rows': at most 2 a row a layer
    assert prefill.prefill_moe_expert_rows % slots == 0 and prefill.prefill_moe_expert_rows >= 4 * slots
    assert 0 < prefill.prefill_moe_held_pairs <= 400 * 2 * 4
    (gap,) = _gaps(SimpleNamespace(app=app, cfg=cfg), served)
    assert len(gap) > 4 and gap.max() < LIMIT, gap


def test_a_preempted_request_rebuilds_its_state_and_window_in_its_prefill():
    cfg = toy_share()
    app, engine = _engine(cfg, 7)
    served = _serve(engine, _prompts(7, (90, 75)), 30, preempt_at=12)
    assert sum(s.request.preemptions for s in served) == 1
    assert max(g.max() for g in _gaps(SimpleNamespace(app=app, cfg=cfg), served)) < LIMIT


def test_the_shares_partial_sums_add_up_to_the_whole_layer():
    """Eight chips of eight experts each: what their routed layers return, each
    with nothing standing in for the others and the shared expert counted once,
    sums to the uncut layer's."""
    import importlib.util

    import jax.numpy as jnp

    path = os.path.join(ROOT, "benchmark", "references", f"{REFERENCE}.py")
    spec = importlib.util.spec_from_file_location("kda_moe_decoder_under_test", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    rng = np.random.default_rng(3)
    H, E, I, S = 64, 64, 32, 48
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3, jnp.float32)  # noqa: E731
    mlp = lambda *lead: {"gate_proj": {"w": draw(*lead, H, I)}, "up_proj": {"w": draw(*lead, H, I)},  # noqa: E731
                         "down_proj": {"w": draw(*lead, I, H)}}
    seg = {"post_attention_layernorm": jnp.ones((1, H)),
           "moe": {"router": {"w": draw(1, H, E), "e_bias": draw(1, E)}, "experts": mlp(1, E),
                   "shared_expert": mlp(1)}}
    x = draw(S, H)

    def routed(first, held, n_shared=1):
        cfg = toy_share(n_routed_experts=held, first_routed_expert=first, n_shared_experts=n_shared)
        part = {**seg, "moe": {**seg["moe"], "experts": {
            k: {"w": v["w"][:, first:first + held]} for k, v in seg["moe"]["experts"].items()}}}
        return ref._build(cfg, frozenset())["routed"](x, part, jnp.int32(0))[0] - x

    whole = routed(0, 64)
    shared = routed(0, 8) - routed(0, 8, n_shared=0)  # what every chip adds alike
    shares = sum(routed(first, 8) for first in range(0, 64, 8)) - 7 * shared
    assert float(jnp.abs(whole - shared).max()) > 0.05 and float(jnp.abs(shared).max()) > 0.05
    np.testing.assert_allclose(np.asarray(shares), np.asarray(whole), atol=5e-5)


@pytest.mark.parametrize("flag, why", [
    (dict(is_prefix_caching=True), "prefix caching"),
    (dict(mixed_dispatch=True), "mixed dispatch"),
    (dict(kv_quant_config={"dtype": "float8_e4m3fn"}), "quantized cache"),
])
def test_the_application_refuses_by_name_what_it_cannot_serve(flag, why):
    cfg = toy_share()
    cfg["benchmark"]["tpu_config"] = flag
    with pytest.raises(NotImplementedError, match=why):
        serving_app.build_app(cfg, [128], seed=1)
