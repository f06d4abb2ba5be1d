"""The configuration ``minicpm-sala-l12`` and what PR 37 adds to read it: its
file against the catalog's row, its cost model on the published shapes, the new
reader on hand-made records, and a toy stage of the model as a whole cell on the
CPU: judged correct, and judged INCORRECT with the selection dropped (dense past
``dense_len``), the forced blocks dropped, the decay's layer factor dropped, a
gate dropped, rope moved onto the sparse layers."""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import cells, correctness, records, serving_app  # noqa: E402
from toy_sala import learned_terms_at_one, toy_stage  # noqa: E402
from toys import quiet_run, toy_steady_cell  # noqa: E402

CELL = "minicpm-sala-l12.longreason-saturated"
CONFIG = cells.read_json("benchmark/configs/minicpm-sala-l12.json")
REFERENCE = "sparse_linear_decoder"
BIG_SEED = 2147483907


def _read(name, run):
    return cells.load_plugin("per_layer", name)(run)


def _run(steps, config=CONFIG):
    return records.RunRecords(
        seconds=10.0, t_open=0.0, t_close=10.0, t_host_end=10.0, setup_s=5.0, served=[],
        population=[], tokens_in_window=0, steps=steps, counters={}, slots=32, pool_blocks=12288,
        block_size=64, tp=1, config=config, traffic={}, device_kind="x", trace=None, notes={},
    )


# -- the configuration file --------------------------------------------------------

def test_the_file_carries_the_catalog_row_but_for_the_depth():
    S, L = "minicpm4", "lightning-attn"
    mixers = [S] + [L] * 8 + [S] + [L] * 6 + [S, S] + [L] * 4 + [S] + [L] * 6 + [S, S, S]
    row = {"attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu",
           "hidden_size": 4096, "intermediate_size": 16384, "lightning_head_dim": 128, "lightning_nh": 32,
           "lightning_nkv": 32, "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
           "max_position_embeddings": 524288, "model_type": "minicpm_sala", "mixer_types": mixers,
           "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
           "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
           "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
           "tie_word_embeddings": False, "use_output_gate": True, "use_output_norm": True,
           "attn_use_output_gate": True}
    assert len(mixers) == 32 and mixers.count(S) == 8
    differs = sorted(k for k, v in row.items() if CONFIG.get(k, "absent") != v)
    assert differs == sorted(CONFIG["reduced"]) == ["num_hidden_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 32}
    first, n = CONFIG["first_hidden_layer"], CONFIG["num_hidden_layers"]
    held = mixers[first: first + n]
    # a window of the published list at the published 1 : 3, starting on a sparse layer
    assert (first, n, CONFIG["num_hidden_layers_total"]) == (9, 12, 32)
    assert held == [S] + [L] * 6 + [S, S] + [L] * 3 and held.count(S) * 3 == held.count(L)
    b = CONFIG["benchmark"]
    assert "tpu_config" not in b  # the family takes its cache tree from its architecture: no knob
    sp = CONFIG["sparse_config"]
    assert b["pa_block_size"] == sp["block_size"]  # a selected block IS a table entry
    traffic = cells.read_json(cells.traffic_path("longreason-saturated"))
    assert traffic["prompt_len"] == {"dist": "uniform", "lo": 8192, "hi": 16384}
    assert traffic["prompt_len"]["lo"] >= sp["dense_len"]  # every decode step of the window selects
    longest = traffic["output_len"]["hi"] + traffic["prompt_len"]["hi"]
    assert longest < b["seq_len"] and b["pa_num_blocks"] * b["pa_block_size"] >= b["slots"] * longest
    for limit in ("logit_mse_tolerance", "logit_tolerance", "served_gap_tolerance", "routing_margin",
                  "logit_tolerance_undecided", "undecided_share_max"):
        assert b[limit] >= 0 and len(b[limit + "_why"]) > 40, limit
    for key in ("sparse_config", "selection", "decay", "qk_norm", "output_norm_and_gates", "rope", "cache"):
        assert len(CONFIG["assumed"][key]) > 40, key


def test_what_the_chip_holds_is_the_issues_arithmetic():
    from nxdi_tpu.models.registry import get_family

    family, cfg_cls = get_family("minicpm_sala")
    published = {k: v for k, v in CONFIG.items() if k not in serving_app.BENCHMARK_KEYS}
    cfg = cfg_cls(serving_app.tpu_config_of(CONFIG, [16384], 16), load_config=lambda: dict(published))
    import jax

    weights = sum(np.prod(s.shape) * 2 for s in jax.tree_util.tree_leaves(family.param_shape_struct(cfg)))
    assert weights == pytest.approx(7.86e9, rel=0.005)  # 3 x 253.8 M + 9 x 285.2 M + 601.7 M, bf16
    arch = family.build_arch(cfg)
    assert [(k, hi - lo) for k, lo, hi, _ in arch.schedule] == [
        ("minicpm4", 1), ("lightning-attn", 6), ("minicpm4", 2), ("lightning-attn", 3)]
    assert arch.sparse.residual_multiplier == pytest.approx(1.4 / 32 ** 0.5)
    # the decay of the first lightning layer held is the PUBLISHED layer 10's
    assert arch.rates[0][0] == pytest.approx(2 ** (-8 / 32) * (1 - 10 / 31 + 1e-5))


# -- the cost model ------------------------------------------------------------------

def test_cost_model_on_the_published_shapes():
    model = cells.load_plugin("cost_model", REFERENCE)
    kernel = cells.load_plugin("cost_model", REFERENCE, "paged_decode_kernel")
    empty = model(CONFIG, 32, 0)
    state = 2 * 9 * 32 * 32 * 128 * 128 * 4  # the lightning state of 32 rows, read and written
    # ISSUE 37's count: 7.86 GB of weights held; a step streams them but for the embedding (0.60 GB)
    assert 7.2e9 < empty["bytes"] - state < 7.3e9 and state == pytest.approx(1.21e9, rel=0.01)
    live = 32 * 15000
    step = model(CONFIG, 32, live)
    chosen = 32 * (64 * 64 - 32)  # 64 blocks a row and KV head, the last half full
    assert step["bytes"] - empty["bytes"] == pytest.approx(3 * (chosen * 1024 + live / 16 * 512))
    dense = model(CONFIG, 32, 32 * 8000)  # under dense_len a row reads what it holds
    assert dense["bytes"] - empty["bytes"] == pytest.approx(3 * (32 * 8000 * 1024 + 32 * 8000 / 16 * 512))
    k = kernel(CONFIG, 32, live)
    assert k["flops"] == pytest.approx(3 * chosen * 4.0 * 32 * 128)
    assert 3 * chosen * 1024 < k["bytes"] < 1.01 * 3 * chosen * 1024  # the selected rows once, q in, o out
    assert k["bytes"] < 0.3 * 3 * live * 1024  # a dense read would be 3.7 times the work: no roofline for it
    assert 14 < k["flops"] / k["bytes"] < 17  # far under the chip's ridge of 240: bound by bytes


# -- the reader ----------------------------------------------------------------------

def _step(read=None, live=None, decode=True):
    return SimpleNamespace(decode={"rows": []} if decode else None, prefills=[],
                           sparse_blocks_read=read, sparse_blocks_live=live)


def test_sparse_read_share_from_the_steps_own_counts():
    steps = [_step(6144, 24000), _step(6144, 25152), _step(100, 100, decode=False), _step()]
    assert _read("attn.sparse_read_pct", _run(steps)) == pytest.approx(100.0 * 12288 / 49152)


@pytest.mark.parametrize("steps", [
    [_step(), _step()],  # a model without block selection records nothing
    [SimpleNamespace(decode={"rows": []}, prefills=[])],  # a StepRecord without the fields (the parent)
    [],
], ids=["no-selection", "old-record", "no-steps"])
def test_sparse_read_share_has_nothing_to_read(steps):
    assert _read("attn.sparse_read_pct", _run(steps)) is None


def test_the_new_cell_is_listed_where_its_readers_find_something():
    manifest = cells.load_manifest()
    cell = cells.resolve(manifest, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"attn.sparse_read_pct", "kernel.paged_decode_ms", "kernel.paged_decode_roofline",
            "kv.bytes_per_live_token", "engine.decode_rows_mean", "kv.pool_used_peak_pct",
            "cte.pad_waste_pct", "tkg.program_roofline", "device.idle_pct"} <= names
    # a traced 3 s of this cell seldom holds one of its ~8 prefills: the reader would find nothing
    assert "cte.device_ms" not in names
    listed = next(m for m in manifest["per_layer"] if m["name"] == "cte.device_ms")["workloads"]
    assert len(listed) == 4 and CELL not in listed
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p99_ms", "out_tok_s", "setup_s"}


# -- a toy stage as a cell on the CPU ------------------------------------------------

def test_a_toy_stage_runs_as_a_cell_and_reads_correct(monkeypatch):
    import jax

    from benchmark import run as bench_run

    quiet_run(monkeypatch)
    cell = toy_steady_cell(toy_stage(), prompt_len={"dist": "lognormal", "median": 60, "sigma": 0.6,
                                                    "lo": 20, "hi": 120})
    said = []
    line = bench_run.run_cell(cell, BIG_SEED, 3.0, False, jax.devices()[:1], said.append)
    assert line["correct"] is True and line["failed"] == 0, said
    assert line["compared"]["strategy_faults"]["value"] == 0
    for name in ("kv.bytes_per_live_token", "attn.sparse_read_pct", "kv.pool_used_peak_pct"):
        assert any(f"per_layer {name}" in s for s in said), (name, said)
    json.dumps(line)


@pytest.fixture(scope="module")
def stage():
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig
    from nxdi_tpu.serving.request import SamplingParams

    cfg, seed = toy_stage(), 12
    app = serving_app.build_app(cfg, [128], seed=seed)
    app.load()
    learned_terms_at_one(app, seed)  # a trained model's gates and keys are O(1)
    engine = InferenceEngine(app, SchedulerConfig(num_slots=4))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 256, size=int(k)).tolist() for k in (100, 40, 70, 120, 90)]
    reqs = [engine.add_request(p, SamplingParams(max_new_tokens=40, eos_token_ids=())) for p in prompts]
    outs = {}
    while engine.has_work():
        for o in engine.step():
            outs[o.request_id] = o
    served = [records.Served(i, 0.0, 0.0, len(p), 40, r, outs[r.request_id], 1.0, prompt=p)
              for i, (p, r) in enumerate(zip(prompts, reqs))]
    got = correctness.program_probe(app, correctness.probe_prompt(seed, 256), 256)
    return SimpleNamespace(cfg=cfg, seed=seed, app=app,
                           samples=correctness.sample_served(served, seed, tokens=100), got=got)


def _judged(s, forward):
    margins = cells.load_plugin("reference", REFERENCE, "routing_margins")
    said = []
    out = correctness.check(s.app.params, s.cfg, forward, s.seed, s.got, s.samples, said.append,
                            routing_margins=margins)
    return out, said


def test_the_toy_stage_reads_correct(stage):
    out, said = _judged(stage, cells.load_plugin("reference", REFERENCE))
    assert out["ok"], said


@pytest.mark.parametrize("term, by", [
    ("selection", "served_gap"), ("forced_blocks", "served_gap"),
    ("decay_layer_factor", "probe_mse"), ("gate", "probe_mse"), ("nope", "probe_mse"),
])
def test_a_model_with_the_term_altered_reads_incorrect(stage, term, by):
    """The reference with one term altered, in the program's place. The 64-token
    probe lies under ``dense_len``: what the selection does is held by the served
    tokens' gap alone; the decay, the gates and the missing rope by the probe."""
    without = cells.load_plugin("reference", REFERENCE, "forward_without")
    out, said = _judged(stage, lambda p, c, ids: without(p, c, ids, term))
    assert not out["ok"], said
    assert out["compared"][by]["value"] > out["compared"][by]["limit"], (by, said)
    with pytest.raises(ValueError, match="unknown terms"):
        without(stage.app.params, stage.cfg, [1, 2, 3], "no-such-term")
