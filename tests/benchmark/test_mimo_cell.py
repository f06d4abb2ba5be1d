"""The configuration ``mimo-v2-flash-ep16`` and what PR 35 adds to read it: its
file against the catalog's row, its cost model on the published shapes, the two
new readers on hand-made records, and a toy share of the model as a whole cell
on the CPU: judged correct, and judged INCORRECT with the sink dropped, the
value scale dropped, the window one position wide, the selection bias dropped."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import cells, correctness, program_trace, records, serving_app  # noqa: E402
from toy_mimo import learned_terms_at_one, toy_share  # noqa: E402
from toys import quiet_run, served_by, toy_steady_cell  # noqa: E402

CELL = "mimo-v2-flash-ep16.longctx-saturated"
CONFIG = cells.read_json("benchmark/configs/mimo-v2-flash-ep16.json")
REFERENCE = "window_moe_decoder"
BIG_SEED = 2147483907


def _read(name, run):
    return cells.load_plugin("per_layer", name)(run)


def _run(steps, config=CONFIG, trace=None, notes=None):
    return records.RunRecords(
        seconds=10.0, t_open=0.0, t_close=10.0, t_host_end=10.0, setup_s=5.0, served=[],
        population=[], tokens_in_window=0, steps=steps, counters={}, slots=128, pool_blocks=6912,
        block_size=128, tp=1, config=config, traffic={}, device_kind="x", trace=trace,
        notes=dict(notes or {}),
    )


# -- the configuration file --------------------------------------------------------

def test_the_file_carries_the_catalog_row_but_for_the_three_cuts():
    full = [0, 1, 1, 1, 1, 0] + ([1] * 5 + [0]) * 7
    row = {"attention_value_scale": 0.707, "hidden_act": "silu", "hidden_size": 4096,
           "intermediate_size": 16384, "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
           "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48, "num_key_value_heads": 4,
           "layernorm_epsilon": 1e-05, "rope_theta": 5000000, "tie_word_embeddings": False,
           "vocab_size": 152576, "partial_rotary_factor": 0.334, "sliding_window": 128,
           "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 128,
           "hybrid_layer_pattern": full, "add_swa_attention_sink_bias": True,
           "add_full_attention_sink_bias": False, "sliding_window_size": 128, "attention_chunk_size": 128,
           "moe_layer_freq": [0] + [1] * 47, "moe_intermediate_size": 2048, "n_routed_experts": 256,
           "n_shared_experts": None, "num_experts_per_tok": 8, "norm_topk_prob": True,
           "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
           "routed_scaling_factor": None, "swa_num_attention_heads": 64, "swa_num_key_value_heads": 8,
           "swa_head_dim": 192, "swa_v_head_dim": 128}
    assert len(full) == 48 and full[:7] == [0, 1, 1, 1, 1, 0, 1]
    differs = sorted(k for k, v in row.items() if CONFIG.get(k, "absent") != v)
    assert differs == sorted(CONFIG["reduced"])
    assert {k: CONFIG["published"][k] for k in differs} == {k: row[k] for k in differs}
    assert (CONFIG["n_routed_experts_total"], CONFIG["first_routed_expert"]) == (256, 0)
    b = CONFIG["benchmark"]
    assert "tpu_config" not in b  # the family takes its cache tree from its architecture: no knob
    traffic = cells.read_json(cells.traffic_path("longctx-saturated"))
    assert traffic["prompt_len"] == {"dist": "uniform", "lo": 2048, "hi": 4096}
    assert traffic["output_len"]["hi"] + traffic["prompt_len"]["hi"] < b["seq_len"]
    # the pool seats every slot at the prompts' longest with room to grow for a window
    assert b["pa_num_blocks"] >= b["slots"] * (traffic["prompt_len"]["hi"] // b["pa_block_size"] + 16)
    for limit in ("logit_mse_tolerance", "logit_tolerance", "served_gap_tolerance", "routing_margin",
                  "logit_tolerance_undecided", "undecided_share_max"):
        assert b[limit] > 0 and len(b[limit + "_why"]) > 40, limit
    for key in ("rope", "attention_value_scale", "sliding_window", "sink", "router", "mtp", "cache"):
        assert len(CONFIG["assumed"][key]) > 40, key


# -- the cost model ------------------------------------------------------------------

def test_cost_model_on_the_published_shapes():
    model = cells.load_plugin("cost_model", REFERENCE)
    kernel = cells.load_plugin("cost_model", REFERENCE, "paged_decode_kernel")
    empty = model(CONFIG, 128, 0)
    # ISSUE 35's count: 6.86 GB of weights held; a step streams them but for the embedding table
    # (0.16 GB: gathered rows) and the experts no row chose (0.969 ** 128 = 1.7 %)
    assert 6.6e9 < empty["bytes"] < 6.9e9
    one_row = model(CONFIG, 1, 0)
    assert one_row["bytes"] < empty["bytes"] - 3.5e9  # one row touches half an expert a layer
    live = 128 * 4500
    step = model(CONFIG, 128, live)
    # 2560 B a live token a full layer, 5120 B a row a window layer for 128 rows of 128 slots
    assert step["bytes"] - empty["bytes"] == pytest.approx(live * 2 * 2560 + 128 * 128 * 5 * 5120)
    assert step["flops"] - empty["flops"] == pytest.approx(40960.0 * (live * 2 + 128 * 128 * 5))
    short = model(CONFIG, 128, 128 * 50)  # shorter than the window: the window layers read what is there
    assert short["bytes"] - empty["bytes"] == pytest.approx(128 * 50 * (2 * 2560 + 5 * 5120))
    k = kernel(CONFIG, 128, live)
    assert k["flops"] == pytest.approx(40960.0 * live * 2)
    assert live * 2 * 2560 < k["bytes"] < 1.01 * live * 2 * 2560  # the live rows once, queries in, result out
    assert 13 < k["flops"] / k["bytes"] < 17  # far under the chip's ridge of 240: bound by bytes


# -- the two readers -----------------------------------------------------------------

def _step(rows, held=None, live=None, prefills=0):
    return SimpleNamespace(
        t_start=0.0, t_end=0.02, prefills=[{}] * prefills, preempted=[], kv_blocks_free=10,
        decode={"rows": [{"slot": i, "request_id": i} for i in range(rows)]} if rows else None,
        kv_bytes_held=held, kv_live_tokens=live, kv_window_rows_held=None if held is None else 128 * rows,
    )


def test_bytes_per_live_token_from_the_steps_own_counts():
    steps = [_step(128, held=7000 * 1000, live=1000), _step(128, held=6900 * 2000, live=2000),
             _step(100, held=7100 * 500, live=500, prefills=1), _step(0, held=5, live=1)]
    assert _read("kv.bytes_per_live_token", _run(steps)) == pytest.approx(7000.0)


@pytest.mark.parametrize("steps", [
    [_step(64), _step(64)],  # a tree that is one pool records nothing
    [SimpleNamespace(decode={"rows": []}, prefills=[])],  # a StepRecord without the fields (the parent)
    [_step(64, held=10, live=0)],  # nothing seated
    [],
], ids=["one-pool", "old-record", "no-live-token", "no-steps"])
def test_bytes_per_live_token_has_nothing_to_read(steps):
    assert _read("kv.bytes_per_live_token", _run(steps)) is None


def _planes(kernel="paged_attention_decode"):
    us = 1000
    tkg = "jit_token_generation_model_8320__abc123_1(77)"
    kern = f"%{kernel}.7 = bf16[128,4,16,128]{{3,2,1,0}} custom-call(%li, %bt)"
    return {"/device:TPU:0": {
        "XLA Modules": [[tkg, 100 * us, 30000 * us], [tkg, 40000 * us, 30000 * us]],
        "XLA Ops": [[kern, 200 * us, 7000 * us], [kern, 9000 * us, 7400 * us],
                    [kern, 41000 * us, 7200 * us], [kern, 50000 * us, 7600 * us],
                    ["%fusion.1 = bf16[8]{0} fusion(%p)", 900 * us, 100 * us]],
    }, "/host:CPU": {"python3": []}}


def test_paged_kernel_roofline_on_a_hand_made_trace(monkeypatch):
    from benchmark import costs

    monkeypatch.setattr(costs, "peaks_of", lambda kind: {"bf16_flops_per_s": 197e12,
                                                         "hbm_bytes_per_s": 819e9})
    notes = {program_trace.NOTE: _planes(), "traced_rows": [128, 128],
             "traced_live_kv_tokens": [570000, 582000]}
    run = _run([], trace=SimpleNamespace(), notes=notes)
    assert _read("kernel.paged_decode_ms", run) == pytest.approx((7.0 + 7.4 + 7.2 + 7.6) / 2)
    kernel = cells.load_plugin("cost_model", REFERENCE, "paged_decode_kernel")
    least = costs.least_s(kernel(CONFIG, 128, 576000), 1, costs.peaks_of("x"))
    assert least["bound"] == "bytes" and 3.5e-3 < least["least_s"] < 3.7e-3
    assert _read("kernel.paged_decode_roofline", run) == pytest.approx(100 * least["least_s"] / 14.6e-3)
    assert _read("kernel.mla_decode_ms", run) is None  # the latent kernel is not on this path


def test_paged_kernel_roofline_has_nothing_to_read(monkeypatch):
    from benchmark import costs

    monkeypatch.setattr(costs, "peaks_of", lambda kind: {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    name = "kernel.paged_decode_roofline"
    assert _read(name, _run([])) is None  # not a traced run
    notes = {"traced_rows": [64], "traced_live_kv_tokens": [1000]}
    other = dict(notes, **{program_trace.NOTE: _planes("mla_paged_decode")})
    assert _read(name, _run([], trace=SimpleNamespace(), notes=other)) is None  # no such kernel
    dense = dict(notes, **{program_trace.NOTE: _planes()})
    dense_config = {"benchmark": {"cost_model": "dense_decoder"}}  # a cost model without the function
    assert _read(name, _run([], config=dense_config, trace=SimpleNamespace(), notes=dense)) is None


# -- a toy share as a cell on the CPU ---------------------------------------------------

def test_a_toy_share_runs_as_a_cell_and_reads_correct(monkeypatch):
    import jax

    from benchmark import run as bench_run

    quiet_run(monkeypatch)
    cell = toy_steady_cell(toy_share())
    said = []
    line = bench_run.run_cell(cell, BIG_SEED, 3.0, False, jax.devices()[:1], said.append)
    assert line["correct"] is True and line["failed"] == 0, said
    assert line["compared"]["strategy_faults"]["value"] == 0
    assert any("tkg_paged_kernel" in s and "tkg_two_part_xla" in s for s in said)
    for name in ("kv.bytes_per_live_token", "moe.pairs_per_held_expert", "kv.pool_used_peak_pct"):
        assert any(f"per_layer {name}" in s for s in said), (name, said)
    json.dumps(line)


@pytest.fixture(scope="module")
def share():
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    cfg, seed = toy_share(), 12
    app = serving_app.build_app(cfg, [256], seed=seed)
    app.load()
    learned_terms_at_one(app, seed)  # a trained model's sinks and selection bias are O(1)
    engine = InferenceEngine(app, SchedulerConfig(num_slots=4))
    served = served_by(engine, seed, requests=5, new=40)
    got = correctness.program_probe(app, correctness.probe_prompt(seed, 256), 256)
    return SimpleNamespace(cfg=cfg, seed=seed, app=app,
                           samples=correctness.sample_served(served, seed, tokens=100), got=got)


def _judged(s, forward):
    margins = cells.load_plugin("reference", REFERENCE, "routing_margins")
    said = []
    out = correctness.check(s.app.params, s.cfg, forward, s.seed, s.got, s.samples, said.append,
                            routing_margins=margins)
    return out, said


def test_the_toy_share_reads_correct(share):
    out, said = _judged(share, cells.load_plugin("reference", REFERENCE))
    assert out["ok"], said


@pytest.mark.parametrize("term", ["sink", "value_scale", "window_off_by_one", "selection_bias"])
def test_a_model_without_the_term_reads_incorrect(share, term):
    """The reference with one term left out, in the program's place: by the
    mean square at the decided positions of the probe, and by the largest
    difference too."""
    without = cells.load_plugin("reference", REFERENCE, "forward_without")
    out, said = _judged(share, lambda p, c, ids: without(p, c, ids, term))
    assert not out["ok"], said
    for number in ("probe_mse", "probe_diff"):
        assert out["compared"][number]["value"] > out["compared"][number]["limit"], (number, said)
    with pytest.raises(ValueError, match="unknown terms"):
        without(share.app.params, share.cfg, [1, 2, 3], "no-such-term")
