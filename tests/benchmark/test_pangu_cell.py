"""The configuration ``pangu-ultra-moe-ep16`` and what PR 30 adds to read it:
its cost model on the published shapes, the three new readers on hand-made
records, and a toy share of the model as a whole cell on the CPU."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import cells, correctness, program_trace, records, serving_app  # noqa: E402
from toys import quiet_run, served_by, toy_config, toy_steady_cell  # noqa: E402

CELL = "pangu-ultra-moe-ep16.reason-saturated"
CONFIG = cells.read_json("benchmark/configs/pangu-ultra-moe-ep16.json")
BIG_SEED = 2147483907


def _read(name, run):
    return cells.load_plugin("per_layer", name)(run)


def _run(steps, config=CONFIG, trace=None, notes=None):
    return records.RunRecords(
        seconds=10.0, t_open=0.0, t_close=10.0, t_host_end=10.0, setup_s=5.0, served=[],
        population=[], tokens_in_window=0, steps=steps, counters={}, slots=128, pool_blocks=2560,
        block_size=128, tp=1, config=config, traffic={}, device_kind="x", trace=trace,
        notes=dict(notes or {}),
    )


def _step(rows, pairs=None, layers=None, prefills=0):
    return SimpleNamespace(
        t_start=0.0, t_end=0.02, prefills=[{}] * prefills, preempted=[], kv_blocks_free=10,
        decode={"rows": [{"slot": i, "request_id": i} for i in range(rows)]} if rows else None,
        moe_held_pairs=pairs, moe_routed_layers=layers,
    )


# -- the configuration file --------------------------------------------------------

def test_the_file_carries_the_catalog_row_but_for_the_four_cuts():
    row = {"attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu", "hidden_size": 7680,
           "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 131072,
           "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048, "n_routed_experts": 256,
           "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
           "num_experts_per_tok": 8, "num_hidden_layers": 61, "num_key_value_heads": 128,
           "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 25600000,
           "routed_scaling_factor": 2.5, "sandwich_norm": True, "tie_word_embeddings": False,
           "v_head_dim": 128, "vocab_size": 153600}
    differs = sorted(k for k, v in row.items() if CONFIG.get(k) != v)
    assert differs == sorted(CONFIG["reduced"])
    assert {k: CONFIG["published"][k] for k in differs} == {k: row[k] for k in differs}
    b = CONFIG["benchmark"]
    traffic = cells.read_json(cells.traffic_path("reason-saturated"))
    longest = traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"]
    assert b["slots"] * -(-longest // b["pa_block_size"]) == b["pa_num_blocks"]  # nothing reserved in vain
    for limit in ("logit_mse_tolerance", "logit_tolerance", "served_gap_tolerance", "routing_margin",
                  "logit_tolerance_undecided", "undecided_share_max"):
        assert b[limit] > 0 and len(b[limit + "_why"]) > 40, limit


# -- the cost model ------------------------------------------------------------------

def test_cost_model_on_the_published_shapes():
    model = cells.load_plugin("cost_model", "mla_moe_decoder")
    kernel = cells.load_plugin("cost_model", "mla_moe_decoder", "mla_decode_kernel")
    empty = model(CONFIG, 128, 0)
    # ISSUE 30's count: 9.5 GB of weights a step (experts 6.04, MLA 1.97, dense MLP 0.85, shared
    # 0.38, head 0.29), a little less where an expert goes untouched (0.969 ** 128 = 1.7 %)
    assert 9.3e9 < empty["bytes"] < 9.6e9
    one_row = model(CONFIG, 1, 0)
    assert one_row["bytes"] < empty["bytes"] - 5.5e9  # one row touches half an expert a layer
    live = 152_000
    step = model(CONFIG, 128, live)
    assert step["bytes"] - empty["bytes"] == live * 5 * 576 * 2  # one 512 + 64 row a token a layer
    assert step["flops"] - empty["flops"] == pytest.approx(278_528 * live * 5)
    k = kernel(CONFIG, 128, live)
    assert k["flops"] == pytest.approx(278_528 * live * 5)
    # the live rows once (0.88 GB) and, a fifth as much again, the queries in and the result out
    assert live * 5 * 576 * 2 < k["bytes"] < 1.25 * live * 5 * 576 * 2
    assert 190 < k["flops"] / k["bytes"] < 245  # near the chip's ridge of 240: bound by both at once


# -- the three readers ---------------------------------------------------------------

def test_pairs_per_held_expert_from_the_steps_own_counts():
    steps = [_step(128, pairs=256, layers=4), _step(128, pairs=240, layers=4),
             _step(100, pairs=272, layers=4, prefills=1), _step(0)]
    # (256 + 240 + 272) / 3 steps / 4 layers / 16 held experts
    assert _read("moe.pairs_per_held_expert", _run(steps)) == pytest.approx(768 / 3 / 4 / 16)


@pytest.mark.parametrize("steps,config", [
    ([_step(64), _step(64)], CONFIG),  # a program that returns no count (the parent, a dense model)
    ([SimpleNamespace(decode={"rows": []}, prefills=[])], CONFIG),  # a StepRecord without the fields
    ([], CONFIG),
    ([_step(64, pairs=10, layers=2)], {"model_type": "qwen2"}),  # no held experts stated
], ids=["no-count", "old-record", "no-steps", "dense-config"])
def test_pairs_per_held_expert_has_nothing_to_read(steps, config):
    assert _read("moe.pairs_per_held_expert", _run(steps, config=config)) is None


def _planes(kernel="mla_paged_decode"):
    us = 1000
    tkg = "jit_token_generation_model_4096__abc123_1(77)"
    kern = f"%{kernel}.7 = bf16[128,128,512]{{2,1,0}} custom-call(%li, %bt)"
    return {"/device:TPU:0": {
        "XLA Modules": [[tkg, 100 * us, 20000 * us], [tkg, 30000 * us, 20000 * us]],
        "XLA Ops": [[kern, 200 * us, 600 * us], [kern, 5000 * us, 500 * us],
                    [kern, 31000 * us, 700 * us], [kern, 36000 * us, 600 * us],
                    ["%fusion.1 = bf16[8]{0} fusion(%p)", 900 * us, 100 * us]],
    }, "/host:CPU": {"python3": []}}


def test_mla_kernel_readers_on_a_hand_made_trace(monkeypatch):
    from benchmark import costs

    monkeypatch.setattr(costs, "peaks_of", lambda kind: {"bf16_flops_per_s": 197e12,
                                                         "hbm_bytes_per_s": 819e9})
    notes = {program_trace.NOTE: _planes(), "traced_rows": [128, 128], "traced_live_kv_tokens": [150000, 154000]}
    run = _run([], trace=SimpleNamespace(), notes=notes)
    assert _read("kernel.mla_decode_ms", run) == pytest.approx((600 + 500 + 700 + 600) / 2 / 1e3)
    kernel = cells.load_plugin("cost_model", "mla_moe_decoder", "mla_decode_kernel")
    least = costs.least_s(kernel(CONFIG, 128, 152000), 1, costs.peaks_of("x"))["least_s"]
    assert 1.0e-3 < least < 1.4e-3  # ISSUE 30: 1.1 ms by the live rows' bytes and by operations
    assert _read("kernel.mla_decode_roofline", run) == pytest.approx(100 * least / 1.2e-3)
    assert _read("kernel.paged_decode_ms", run) is None  # the dense models' kernel is not on this path


@pytest.mark.parametrize("name", ["kernel.mla_decode_ms", "kernel.mla_decode_roofline"])
def test_mla_kernel_readers_have_nothing_to_read(name, monkeypatch):
    from benchmark import costs

    monkeypatch.setattr(costs, "peaks_of", lambda kind: {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert _read(name, _run([])) is None  # not a traced run
    notes = {"traced_rows": [64], "traced_live_kv_tokens": [1000]}
    other = dict(notes, **{program_trace.NOTE: _planes("paged_attention_decode")})
    assert _read(name, _run([], trace=SimpleNamespace(), notes=other)) is None  # no such kernel
    dense = dict(notes, **{program_trace.NOTE: _planes()})
    dense_config = {"benchmark": {"cost_model": "dense_decoder"}}
    if name.endswith("roofline"):  # a cost model without the kernel's function
        assert _read(name, _run([], config=dense_config, trace=SimpleNamespace(), notes=dense)) is None


# -- a toy share as a cell on the CPU ---------------------------------------------------

def toy_share(**published):
    """Hidden 64, 4 heads, 2 dense + 2 expert layers, 16 experts of which 4
    are held (4..7), top 2: through the harness exactly as the cell is."""
    cfg = toy_config(
        "pangu_ultra_moe", num_hidden_layers=4, first_k_dense_replace=2, num_key_value_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        moe_intermediate_size=32, n_routed_experts=4, n_routed_experts_total=16,
        first_routed_expert=4, n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=25600000, sandwich_norm=True,
        hidden_act="silu", num_nextn_predict_layers=1, attention_bias=False,
    )
    # the limits, from this toy's own readings on the CPU (seeds 7, 12, BIG_SEED): decided positions
    # read probe_diff <= 0.008 and probe_mse 2e-6; the router without renormalisation 0.03-0.05 at
    # every position; a score moves by ~3e-4 under bf16
    cfg["benchmark"].update(
        reference="mla_moe_decoder", cost_model="mla_moe_decoder",  # the default (sparse) dispatch, as the cell
        attention_strategies={"context_encoding_model": "cte_flash_kernel",
                              "token_generation_model": "tkg_mla_paged_kernel"},
        logit_mse_tolerance=1e-5, logit_tolerance=0.02, served_gap_tolerance=0.02,
        routing_margin=2e-3, logit_tolerance_undecided=0.3, undecided_share_max=0.5,
    )
    cfg.update(published)
    return cfg


def test_a_toy_share_runs_as_a_cell_and_reads_correct(monkeypatch):
    import jax

    from benchmark import run as bench_run

    quiet_run(monkeypatch)
    cell = toy_steady_cell(toy_share())
    said = []
    line = bench_run.run_cell(cell, BIG_SEED, 3.0, False, jax.devices()[:1], said.append)
    assert line["correct"] is True and line["failed"] == 0, said
    assert list(line["compared"])[:6] == ["probe_mse", "probe_diff", "served_gap", "probe_diff_undecided",
                                          "served_gap_undecided", "undecided_share"]
    assert line["compared"]["strategy_faults"]["value"] == 0
    assert any("tkg_mla_paged_kernel" in s for s in said) and any("cte_flash_kernel" in s for s in said)
    # every reader of the manifest was called on this run: the new ones among them
    assert any("per_layer moe.pairs_per_held_expert" in s for s in said), said
    json.dumps(line)


@pytest.fixture(scope="module")
def share():
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    cfg, seed = toy_share(), 12
    # this seed's own readings: 1.5e-6 and 0.0055 at decided positions; the router without
    # renormalisation 9.1e-6 and 0.0151
    cfg["benchmark"].update(logit_mse_tolerance=4e-6, logit_tolerance=0.011)
    app = serving_app.build_app(cfg, [256], seed=seed)
    app.load()
    engine = InferenceEngine(app, SchedulerConfig(num_slots=4))
    served = served_by(engine, seed, requests=5, new=24)
    got = correctness.program_probe(app, correctness.probe_prompt(seed, 256), 256)
    return SimpleNamespace(cfg=cfg, seed=seed, app=app, engine=engine,
                           samples=correctness.sample_served(served, seed, tokens=100), got=got)


def test_a_router_without_renormalisation_reads_incorrect(share):
    s = share
    forward = cells.load_plugin("reference", "mla_moe_decoder")
    margins = cells.load_plugin("reference", "mla_moe_decoder", "routing_margins")
    wrong = cells.load_plugin("reference", "mla_moe_decoder", "forward_without_renormalisation")
    said = []
    assert correctness.check(s.app.params, s.cfg, forward, s.seed, s.got, s.samples, said.append,
                             routing_margins=margins)["ok"], said
    out = correctness.check(s.app.params, s.cfg, wrong, s.seed, s.got, s.samples, said.append,
                            routing_margins=margins)
    assert not out["ok"], said
    for number in ("probe_mse", "probe_diff"):  # at the DECIDED positions, by both
        assert out["compared"][number]["value"] > out["compared"][number]["limit"]


def test_the_engine_records_the_held_pairs_and_counts_them(share):
    recs = [r for r in share.engine.flight.snapshot_records() if r.decode is not None]
    assert recs and all(r.moe_routed_layers == 2 for r in recs)
    # 4 rows (padding included) x top 2 x 2 layers at the most; 4 of 16 experts held: a quarter on average
    assert all(0 <= r.moe_held_pairs <= 16 for r in recs)
    assert 0.05 < sum(r.moe_held_pairs for r in recs) / len(recs) / 2 / 4 < 2.0
    assert recs[0].to_dict()["moe_held_pairs"] == recs[0].moe_held_pairs
    from benchmark.run import counter_values

    counters = counter_values(share.app.telemetry.registry)
    pairs = sum(r.moe_held_pairs for r in recs)
    assert counters["nxdi_moe_held_pairs_total|"] == pairs
    assert counters["nxdi_moe_routed_layers_steps_total|"] == 2 * len(recs)
    run = _run(recs, config=share.cfg)
    assert _read("moe.pairs_per_held_expert", run) == pytest.approx(pairs / len(recs) / 2 / 4)


def test_a_dense_model_returns_and_records_no_count():
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    app = serving_app.build_app(toy_config("qwen2"), [256], seed=5)
    app.load()
    engine = InferenceEngine(app, SchedulerConfig(num_slots=4))
    served_by(engine, 5, requests=2, new=4)
    recs = [r for r in engine.flight.snapshot_records() if r.decode is not None]
    assert recs and all(r.moe_held_pairs is None and r.moe_routed_layers is None for r in recs)
    assert "nxdi_moe_held_pairs_total" not in app.telemetry.prometheus_text()
