"""Is the loaded app the model its configuration says? Two comparisons with
the configuration's plain reference, before the window and outside the timing:

(a) all-position logits of a seeded prompt through the program's own logit
    probe (``utils.accuracy.probe_all_logits``) against the reference, by
    largest absolute difference;
(b) the same prompt served through the engine for a few greedy tokens, then
    the reference's full forward over prompt + those tokens: each served
    token's reference logit must lie within the tolerance of that position's
    largest. Decoding through the paged cache is held to the reference without
    comparing token ids (bf16 logits tie exactly, PERF.md section 6).

The probe is lent the app's own KV pool and hands it back: a second pool would
not fit beside the first at a deployment's size.
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np

PROBE_TOKENS = 64
SERVED_TOKENS = 8


def lend_pool_to_probe(app):
    """Build the program's logit probe around the app's own cache (what
    ``_get_logit_probe`` does, minus the second pool)."""
    from nxdi_tpu.parallel.layers import sharding_tree
    from nxdi_tpu.runtime.model_wrapper import ModelWrapper

    wrapper = app.models["context_encoding_model"]
    fkw = dict(wrapper.forward_kwargs)
    fkw.update(output_all_logits=True, output_logits=True)
    probe = ModelWrapper(
        wrapper.tag + "_logit_probe", wrapper.config, wrapper.arch, wrapper.inv_freq,
        batch_size=wrapper.batch_size, n_active_tokens=0, buckets=wrapper.buckets[:1],
        attend_to_cache=False, forward_fn=wrapper.forward_fn, forward_kwargs=fkw,
    )
    probe.build(
        app.mesh,
        sharding_tree(app.param_specs(), app.mesh),
        sharding_tree(app.cache_partition_specs(), app.mesh),
    )
    app._logit_probe = (probe, app.kv_cache)
    app.kv_cache = None


def take_pool_back(app) -> None:
    _, cache = app._logit_probe
    app.kv_cache = cache
    app._logit_probe = None


def check(app, engine, config: dict, reference: Callable, seed: int, say) -> Dict[str, object]:
    """``{"ok": bool, "probe_max_abs_diff": ..., "served_worst_gap": ...}``."""
    import jax

    from nxdi_tpu.serving.request import SamplingParams
    from nxdi_tpu.utils.accuracy import probe_all_logits

    tol = float(config["benchmark"]["logit_tolerance"])
    vocab = config["vocab_size"]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    prompt = rng.integers(0, vocab, size=PROBE_TOKENS)

    lend_pool_to_probe(app)
    try:
        got = probe_all_logits(app, prompt[None, :])[0][:, :vocab].astype(np.float32)
    finally:
        take_pool_back(app)

    req = engine.add_request(
        prompt.tolist(), SamplingParams(max_new_tokens=SERVED_TOKENS, eos_token_ids=())
    )
    outs = []
    while engine.has_work():
        outs.extend(engine.step())
    served = list(outs[0].token_ids) if outs else []
    del req

    full = np.concatenate([prompt, np.asarray(served, dtype=prompt.dtype)])
    ref = np.asarray(jax.device_get(reference(app.params, config, full)), dtype=np.float32)

    finite = bool(np.isfinite(got).all() and np.isfinite(ref).all())
    probe_diff = float(np.abs(got - ref[:PROBE_TOKENS]).max())
    spread = float(ref[:PROBE_TOKENS].std())
    # served token i was sampled at position PROBE_TOKENS - 1 + i
    gaps = [
        float(ref[PROBE_TOKENS - 1 + i].max() - ref[PROBE_TOKENS - 1 + i, tok])
        for i, tok in enumerate(served)
    ]
    worst_gap = max(gaps) if gaps else float("inf")
    ok = (
        finite and len(served) == SERVED_TOKENS
        and probe_diff <= tol and worst_gap <= tol
    )
    say(f"correctness: probe logits vs float32 reference over {PROBE_TOKENS} positions: "
        f"max |diff| {probe_diff:.4f} (tolerance {tol}, reference logit std {spread:.3f}); "
        f"{len(served)} served tokens, worst reference gap to the top logit {worst_gap:.4f}; "
        f"{'ok' if ok else 'FAILED'}")
    return {"ok": ok, "probe_max_abs_diff": probe_diff, "served_worst_gap": worst_gap,
            "reference_logit_std": spread}
