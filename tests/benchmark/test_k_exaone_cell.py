"""The configuration ``k-exaone-ep8`` and what the benchmark adds to read it: its
file against the catalog's row (the three cuts, the 48-entry lists verbatim,
the guide's floors on ``num_experts``), the sizing arithmetic, its cost model on
the published shapes, the new reader ``tkg.window_attention_ms`` on a
synthetic trace and on a recorded one, and a toy share of the model as a whole
cell on the CPU, judged correct."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import cells, program_regions, program_trace, records, serving_app  # noqa: E402
from toy_exaone import toy_share  # noqa: E402
from toys import quiet_run, toy_steady_cell  # noqa: E402

CELL = "k-exaone-ep8.longctx-saturated"
MIMO = "mimo-v2-flash-ep16.longctx-saturated"
CONFIG = cells.read_json("benchmark/configs/k-exaone-ep8.json")
REFERENCE = "exaone_moe_decoder"
BIG_SEED = 2147483907
LLLG = ["sliding_attention"] * 3 + ["full_attention"]
#: the catalog's row (architectures.jsonl), as published
ROW = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 6144,
    "intermediate_size": 18432, "layer_types": LLLG * 12, "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47, "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12, "tie_word_embeddings": False, "topk_group": 1,
    "vocab_size": 153600,
}


def _run(trace=None, notes=None, config=CONFIG):
    return records.RunRecords(
        seconds=10.0, t_open=0.0, t_close=10.0, t_host_end=10.0, setup_s=5.0, served=[],
        population=[], tokens_in_window=0, steps=[], counters={}, slots=128, pool_blocks=6912,
        block_size=128, tp=1, config=config, traffic={}, device_kind="TPU v5 lite", trace=trace,
        notes=dict(notes or {}),
    )


# -- the configuration file --------------------------------------------------------

def test_the_file_carries_the_catalog_row_but_for_the_three_cuts():
    differs = sorted(k for k, v in ROW.items() if CONFIG.get(k, "absent") != v)
    assert differs == sorted(CONFIG["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert CONFIG["published"] == {k: ROW[k] for k in CONFIG["reduced"]}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"], CONFIG["vocab_size"]) == (5, 16, 19200)
    assert (CONFIG["num_experts_total"], CONFIG["first_routed_expert"]) == (128, 0)
    assert CONFIG["n_routed_experts"] == CONFIG["num_experts"]  # what moe.pairs_per_held_expert divides by
    manifest = next(c for c in cells.load_manifest()["configs"] if c["name"] == "k-exaone-ep8")
    assert manifest["reduced"] == CONFIG["reduced"] and manifest["source"] == CONFIG["source"]
    b = CONFIG["benchmark"]
    assert "tpu_config" not in b  # the family takes its cache tree from its architecture: no knob
    traffic = cells.read_json(cells.traffic_path("longctx-saturated"))
    assert traffic["output_len"]["hi"] + traffic["prompt_len"]["hi"] < b["seq_len"]
    # the pool seats every slot at the prompts' longest with room to grow for a window
    assert b["pa_num_blocks"] >= b["slots"] * (traffic["prompt_len"]["hi"] // b["pa_block_size"] + 16)
    assert b["attention_strategies"] == {"context_encoding_model": "cte_flash_kernel",
                                         "token_generation_model": "tkg_paged_kernel"}
    for limit in ("logit_mse_tolerance", "logit_tolerance", "served_gap_tolerance", "routing_margin",
                  "logit_tolerance_undecided", "undecided_share_max"):
        assert b[limit] > 0 and len(b[limit + "_why"]) > 40, limit
    for key in ("norms", "rope", "router", "mtp", "sliding_window", "num_experts_total", "slots", "cache",
                "weights"):
        assert len(CONFIG["assumed"][key]) > 40, key


@pytest.mark.parametrize("key", ["layer_types", "mlp_layer_types", "sliding_windows"])
def test_the_published_lists_are_kept_verbatim_and_read_up_to_the_depth(key):
    from nxdi_tpu.models.registry import get_family

    assert len(CONFIG[key]) == 48 and CONFIG[key] == ROW[key]
    _, cfg_cls = get_family("exaone_moe")
    published = {k: v for k, v in CONFIG.items() if k not in serving_app.BENCHMARK_KEYS}
    cfg = cfg_cls(serving_app.tpu_config_of(CONFIG, [4096], 16), load_config=lambda: dict(published))
    assert getattr(cfg, key) == ROW[key][:5]


def test_the_guides_floors_hold_on_num_experts():
    """A whole period of LLLG after the leading dense layer and at least four
    routed layers, at least 8 held experts (the key that counts them here is
    ``num_experts``), the router at its published width, an eighth of the
    vocabulary."""
    n, published = CONFIG["num_hidden_layers"], CONFIG["published"]
    dense = CONFIG["first_k_dense_replace"]
    assert CONFIG["mlp_layer_types"][:n].count("sparse") == n - dense >= 4
    assert sorted(CONFIG["layer_types"][dense:n]) == sorted(LLLG)  # 3 window : 1 full, as published
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["num_experts_total"] == published["num_experts"]
    assert 0 <= CONFIG["first_routed_expert"] <= published["num_experts"] - CONFIG["num_experts"]
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert CONFIG["num_experts_per_tok"] == ROW["num_experts_per_tok"]


def test_what_the_chip_holds_is_the_sizing_arithmetic():
    import jax

    from nxdi_tpu.models.registry import get_family

    family, cfg_cls = get_family("exaone_moe")
    published = {k: v for k, v in CONFIG.items() if k not in serving_app.BENCHMARK_KEYS}
    cfg = cfg_cls(serving_app.tpu_config_of(CONFIG, [4096], 16), load_config=lambda: dict(published))
    struct = family.param_shape_struct(cfg)
    params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(struct))
    # embedding + head 235.9 M, layer 0 453.0 M, four routed layers of 755.8 M
    assert params == pytest.approx(3.712e9, rel=0.001)
    arch = family.build_arch(cfg)
    assert [(k, hi - lo) for k, lo, hi, _ in arch.schedule] == [("swa", 1), ("swa", 2), ("full", 1), ("swa", 1)]
    moe = arch.full.moe
    assert (moe.num_experts, moe.experts_here, moe.top_k, moe.first_held) == (128, 16, 8, 0)
    assert moe.shared_expert_intermediate_size == 2048 and moe.correction_bias and moe.routed_scaling == 2.5
    app = family.APPLICATION_CLS("<shapes>", cfg, model_family=family)
    cache = {k: (s.shape, str(s.dtype)) for k, s in app._cache_struct().items()}
    assert cache == {
        "k": ((1, 884736, 8, 128), "bfloat16"), "v": ((1, 884736, 8, 128), "bfloat16"),
        "k_swa": ((4, 128, 8, 128, 128), "bfloat16"), "v_swa": ((4, 128, 8, 128, 128), "bfloat16"),
    }
    held = sum(leaf.size * leaf.dtype.itemsize
               for tree in (struct, app._cache_struct()) for leaf in jax.tree_util.tree_leaves(tree))
    assert held / 16e9 == pytest.approx(0.707, abs=0.002)  # 7.42 GB + 3.62 GB + 0.27 GB


# -- the cost model ------------------------------------------------------------------

def test_cost_model_on_the_published_shapes():
    model = cells.load_plugin("cost_model", REFERENCE)
    paged = cells.load_plugin("cost_model", REFERENCE, "paged_decode_kernel")
    live = 128 * 4500
    step = model(CONFIG, 128, live)
    # every held expert streams at 128 rows x top 8 of 128: the whole share but the embedding (a
    # step gathers its rows), 7.2 GB, and the pool
    weights = 2 * (3.712e9 - 19200 * 6144)
    assert step["bytes"] == pytest.approx(weights + live * 4096 + 4 * 128 * 128 * 4096, rel=0.01)
    assert 11e-3 < step["bytes"] / 819e9 < 13e-3  # the byte floor of a step at 3-6k live tokens a slot
    p = paged(CONFIG, 128, live)
    assert p["bytes"] == pytest.approx(live * 4096 + 128 * 64 * 128 * 4)
    assert p["flops"] == pytest.approx(live * 4.0 * 64 * 128)


# -- the new reader ------------------------------------------------------------------

def _device(commit_scope):
    """Two executions of a token-generation module and one of a prefill, each
    with the same ops: window attention and the ring's commit under
    ``commit_scope``, full attention, window FFN, an unscoped slice."""
    tkg, cte = 1, 2
    ops = {
        10: "jit(token_generation_model)/layers.window/layers/while/body/attn.block/attn.core/dot_general",
        11: f"jit(token_generation_model)/{commit_scope}/kv.write/kv_commit_rows",
        12: "jit(token_generation_model)/layers.full/layers/while/body/attn.block/attn.core/paged",
        13: "jit(token_generation_model)/layers.window/layers/while/body/ffn.block/moe.experts.dense/dot",
        14: "jit(token_generation_model)/layers.window/layers/while/body/dynamic_slice",
    }
    dur = {10: 2_000_000, 11: 500_000, 12: 3_000_000, 13: 1_000_000, 14: 250_000}
    modules, events = [], []
    for mod, t0 in ((tkg, 0), (cte, 20_000_000), (tkg, 40_000_000)):
        modules.append([mod, t0, 10_000_000])
        t = t0
        for mid, d in dur.items():
            events.append([mid, t, d])
            t += d
    return program_regions.Device(
        names={tkg: "jit_token_generation_model_8320__0123456789ab_1",
               cte: "jit_context_encoding_model_4096__0123456789ab_2", **{m: f"fusion.{m}" for m in ops}},
        tf_op=ops, modules=modules, ops=events,
    )


def _reader():
    return cells.load_plugin("per_layer", "tkg.window_attention_ms")


@pytest.mark.parametrize("commit_scope, want_ps", [("layers.window", 2_500_000), ("", 2_000_000)],
                         ids=["commit-under-the-window", "commit-outside"])
def test_the_reader_splits_window_attention_from_full_attention(commit_scope, want_ps, monkeypatch):
    """Per token-generation execution: the window layers' attention ops and,
    where it lies under their container, the ring's commit; never the full
    layer's attention, the window layers' FFN, an unscoped op or a prefill's."""
    import importlib.util

    path = cells.plugin_path("per_layer", "tkg.window_attention_ms")
    spec = importlib.util.spec_from_file_location("window_attention_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.window_attention_ms(_device(commit_scope)) == pytest.approx(want_ps / 1e9)
    dev = _device("layers.window")
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: "a.xplane.pb")
    monkeypatch.setattr(program_regions, "read_device", lambda path: dev)
    run = _run(trace=object())
    assert _reader()(run) == pytest.approx(2.5e-3)
    assert run.notes["tkg.window_attention_ms"] == pytest.approx(2.5e-3)  # read once a run


def test_the_reader_has_nothing_to_read_without_window_scopes_or_a_trace(monkeypatch):
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded_regions_v5e.json")) as f:
        rec = json.load(f)  # a dense model's traced decode: no window layer
    qwen = program_regions.Device(
        names={int(k): v for k, v in rec["names"].items()},
        tf_op={int(k): v for k, v in rec["tf_op"].items()}, modules=rec["modules"], ops=rec["ops"])
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: "a.xplane.pb")
    monkeypatch.setattr(program_regions, "read_device", lambda path: qwen)
    assert _reader()(_run(trace=object())) is None
    unscoped = _device("layers.window")
    unscoped.tf_op = {}  # a program served from the persistent cache: no name stacks
    monkeypatch.setattr(program_regions, "read_device", lambda path: unscoped)
    assert _reader()(_run(trace=object())) is None
    assert _reader()(_run(trace=None)) is None  # an untraced run


def test_the_new_cell_and_reader_are_listed_where_they_read():
    manifest = cells.load_manifest()
    cell = cells.resolve(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "longctx-saturated" and cell.config_name == "k-exaone-ep8"
    names = {m["name"] for m in cell.per_layer}
    assert {"tkg.window_attention_ms", "kernel.paged_decode_ms", "kernel.paged_decode_roofline",
            "moe.pairs_per_held_expert", "kv.bytes_per_live_token", "engine.decode_rows_mean",
            "kv.pool_used_peak_pct", "cte.pad_waste_pct", "tkg.attention_ms", "device.idle_pct"} <= names
    # the latent, sparse and KDA readers are other cells'; the two prefill lists are pinned elsewhere
    assert not names & {"moe.prefill_rows_per_live_pair", "cte.device_ms", "kernel.mla_decode_ms",
                        "attn.sparse_read_pct", "kernel.kda_decode_ms"}
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p99_ms", "out_tok_s", "setup_s"}
    entry = next(m for m in manifest["per_layer"] if m["name"] == "tkg.window_attention_ms")
    assert entry == {"name": "tkg.window_attention_ms", "unit": "ms", "better": "lower", "source": "device_trace",
                     "layer": "model step programs", "moves": "tpot_p99_ms", "workloads": [MIMO, CELL]}
    assert manifest["per_layer"][-1] is entry and manifest["workloads"][-1]["name"] == CELL


# -- a toy share as a cell on the CPU ------------------------------------------------

def test_a_toy_share_runs_as_a_cell_and_reads_correct(monkeypatch):
    import jax

    from benchmark import run as bench_run

    quiet_run(monkeypatch)
    cell = toy_steady_cell(toy_share(), prompt_len={"dist": "lognormal", "median": 60, "sigma": 0.6,
                                                    "lo": 20, "hi": 120})
    said = []
    line = bench_run.run_cell(cell, BIG_SEED, 3.0, False, jax.devices()[:1], said.append)
    assert line["correct"] is True and line["failed"] == 0, said
    assert line["compared"]["strategy_faults"]["value"] == 0
    for name in ("kv.bytes_per_live_token", "moe.pairs_per_held_expert", "kv.pool_used_peak_pct"):
        assert any(f"per_layer {name}" in s for s in said), (name, said)
    json.dumps(line)
