"""Shared probe harness: the random-weight Llama-3.2-1B bench app and the
device-resident chain timing discipline (one ``barrier`` per timed chain;
see the note in bench.py ``main``).

Every on-chip probe script (decode_ablation, multistep_probe, kernel_ab,
cte_probe, spec8b_probe) builds its model and timing loop from here so the
bench discipline and the reference 1B geometry live in ONE place."""

import sys
import time

import numpy as np

HIDDEN, INTER, LAYERS = 2048, 8192, 16
HEADS, KV_HEADS, HEAD_DIM = 32, 8, 64
VOCAB = 128256


def barrier(out) -> None:
    """Completion barrier of a dispatch (chain): ``block_until_ready`` on
    its tokens — sound on the v5e runtime (scripts/sync_barrier_probe.py)."""
    import jax

    jax.block_until_ready(out["tokens"])


def build_random_app(
    batch=32,
    seq_len=2048,
    prompt_len=1024,
    vocab=VOCAB,
    inter=INTER,
    layers=LAYERS,
    seed=0,
    **tcfg_extra,
):
    """Random-weight full-depth 1B-geometry llama app on the current backend.
    Returns (app, rng, prompt, pos) with the CTE already run once."""
    import jax.tree_util as jtu
    import ml_dtypes

    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
    from nxdi_tpu.models.llama import modeling_llama as ml
    from nxdi_tpu.runtime.application import TpuModelForCausalLM, params_shape_struct

    defaults = dict(
        tp_degree=1, batch_size=batch, seq_len=seq_len,
        max_context_length=prompt_len, dtype="bfloat16",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        async_mode=True, attn_kernel_enabled=True, fused_qkv=True,
        skip_warmup=True,
    )
    defaults.update(tcfg_extra)
    tcfg = TpuConfig(**defaults)
    cfg = ml.LlamaInferenceConfig(
        tcfg, hidden_size=HIDDEN, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=HEADS,
        num_key_value_heads=KV_HEADS, head_dim=HEAD_DIM,
        vocab_size=vocab, rms_norm_eps=1e-5, rope_theta=500000.0,
    )
    rng = np.random.default_rng(seed)
    struct = params_shape_struct(ml, cfg, ml.build_arch(cfg))
    state = jtu.tree_map(
        lambda s: (rng.standard_normal(s.shape, dtype=np.float32) * 0.02).astype(
            ml_dtypes.bfloat16
        ),
        struct,
    )

    class App(TpuModelForCausalLM):
        def build_params(self):
            return state

    app = App("<random>", cfg, model_family=ml)
    app.load()
    prompt = rng.integers(
        0, min(32000, vocab - 1), size=(batch, prompt_len)
    ).astype(np.int32)
    pos = np.tile(np.arange(prompt_len, dtype=np.int32), (batch, 1))
    out = app.forward(
        prompt, pos, last_token_index=np.full((batch,), prompt_len - 1, np.int32)
    )
    barrier(out)
    app._probe_first_out = out
    return app, rng, prompt, pos


def metrics_out_requested(argv=None) -> bool:
    return "--metrics-out" in (argv if argv is not None else sys.argv)


def maybe_dump_metrics(entries, argv=None):
    """``--metrics-out FILE``: dump telemetry JSON snapshot(s) next to the
    probe's latency lines. ``entries`` maps label -> a loaded app (whose
    telemetry is snapshotted here) OR a pre-collected snapshot dict (for
    apps already deleted to free HBM). Returns the path written, or None
    when the flag is absent."""
    import json

    argv = argv if argv is not None else sys.argv
    if "--metrics-out" not in argv:
        return None
    i = argv.index("--metrics-out")
    if i + 1 >= len(argv):
        raise SystemExit("--metrics-out needs a FILE argument")
    path = argv[i + 1]
    snaps = {
        label: (v if isinstance(v, dict) else v.telemetry.snapshot())
        for label, v in entries.items()
    }
    with open(path, "w") as f:
        json.dump(snaps, f, indent=2)
    print(f"[metrics] telemetry snapshot -> {path}", file=sys.stderr, flush=True)
    return path


def median_chain_ms(app, seq_len, warmup=20, steps=100, reps=3, label=None):
    """Decode p50 ms/step over device-resident chains (bench.py discipline)."""
    from nxdi_tpu.runtime.model_wrapper import TAG_TOKEN_GENERATION

    w = app.models[TAG_TOKEN_GENERATION]
    out = app._probe_first_out
    nxt = out["next_inputs"]
    for _ in range(warmup):
        out, app.kv_cache = w.forward_device(app.params, app.kv_cache, nxt, seq_len)
        nxt = out["next_inputs"]
    barrier(out)
    per = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            out, app.kv_cache = w.forward_device(
                app.params, app.kv_cache, nxt, seq_len
            )
            nxt = out["next_inputs"]
        barrier(out)
        per.append((time.perf_counter() - t0) * 1000.0 / steps)
    ms = round(float(np.percentile(per, 50)), 3)
    if label:
        print(f"[{label}] {ms} ms", file=sys.stderr, flush=True)
    return ms
