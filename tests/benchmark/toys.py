"""Toy configurations and a few served requests for the benchmark's CPU tests."""

import numpy as np

from benchmark import cells, costs, records


def toy_config(model_type, **published):
    cfg = dict(
        name="toy", model_type=model_type, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        max_position_embeddings=4096, rms_norm_eps=1e-6, rope_theta=1e6,
        tie_word_embeddings=model_type == "qwen2", sliding_window=None, use_sliding_window=False,
        reduced=[], source="nowhere", deployment="toy", assumed={},
        benchmark=dict(
            chips=1, tp=1, reference="dense_decoder", cost_model="dense_decoder", seq_len=512,
            slots=4, ctx_batch_size=1, pa_block_size=128, pa_num_blocks=24,
            logit_mse_tolerance=1e-5, logit_tolerance=0.05, served_gap_tolerance=0.05,
            attention_strategies={"context_encoding_model": "cte_flash_kernel",
                                  "token_generation_model": "tkg_paged_kernel"},
        ),
    )
    cfg.update(published)
    return cfg


def served_by(engine, seed, requests=6, new=40, vocab=256):
    """A few requests through the engine, as ``drive`` records them."""
    from nxdi_tpu.serving.request import SamplingParams

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=int(k)).tolist() for k in rng.integers(20, 200, size=requests)]
    reqs = [engine.add_request(p, SamplingParams(max_new_tokens=new, eos_token_ids=())) for p in prompts]
    outs = {}
    while engine.has_work():
        for o in engine.step():
            outs[o.request_id] = o
    return [records.Served(i, 0.0, 0.0, len(p), new, r, outs[r.request_id], 1.0, prompt=p)
            for i, (p, r) in enumerate(zip(prompts, reqs))]


def quiet_run(monkeypatch):
    """A run owns its process; a test does not: leave this worker's JAX as it
    was (no persistent compile cache for the tests that follow, no listener
    left on) and give the CPU a row in the table of peaks."""
    import jax

    import nxdi_tpu.runtime.application as application

    monkeypatch.setattr(application, "enable_persistent_cache", lambda: "(off in tests)")
    monkeypatch.setattr(jax.monitoring, "register_event_duration_secs_listener", lambda cb: None)
    monkeypatch.setattr(costs, "peaks_of", lambda kind: {"bf16_flops_per_s": 1.0,
                                                         "hbm_bytes_per_s": 1.0})


def toy_steady_cell(config, **traffic_changes):
    """``chat-steady`` cut to what a CPU serves in seconds, every metric of the
    manifest reported, under ``config``."""
    traffic = cells.read_json(cells.traffic_path("chat-steady"))
    traffic.update(rate_per_s=3.0, drain_cap_s=30)
    traffic["prompt_len"] = dict(traffic["prompt_len"], hi=200)
    traffic["output_len"] = dict(traffic["output_len"], median=8, lo=2, hi=12)
    traffic.update(traffic_changes)
    manifest = cells.load_manifest()
    return cells.Cell(
        "toy.chat-steady", "toy", config, "chat-steady", traffic, 1,
        [dict(m, workloads=None) for m in manifest["end_to_end"]], list(manifest["per_layer"]),
    )
