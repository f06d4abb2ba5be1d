"""Is what the window served the model its configuration says? Decided once
the window has closed, by the configuration's plain reference (float32, no
kernel, no cache, nothing of the program's), over two things the program made:

(a) the served tokens of a sample of the requests the window finished: the
    longest of them, then draws from the seed until ``SAMPLE_TOKENS`` served
    tokens are in it. The reference runs once over each prompt with its served
    tokens; at every served position the gap by which the served token's
    reference logit lies below that position's largest is read (``served_gap``
    is the widest). Token ids are not compared: bf16 logits tie. This is the
    timed path itself: the engine's prefill and its decoding through the paged
    cache, in the window's own batches.
(b) all-position logits of a seeded prompt through the program's own logit
    probe (``utils.accuracy.probe_all_logits``): the mean squared difference
    over every position and the whole vocabulary (``probe_mse``: roundings add
    in squares, and it is steady from seed to seed, so this is the number the
    lower-precision control fails) and the largest absolute one
    (``probe_diff``: one wrong logit). A second program, kept because the timed
    path hands out token ids only and a few hundred of those cannot tell int8
    from bf16 (PERF.md section 2). It is lent the app's own cache and hands it back.

Where the reference file also defines ``routing_margins`` (a model with routed
experts), a position whose smallest router gap lies under the configuration's
``routing_margin`` is UNDECIDED: one expert swapped there is rounding, not a
fault. Decided positions are held to the tight limits, undecided ones to
``logit_tolerance_undecided``, and their share to ``undecided_share_max``.
Nothing of the program's routing is read. Without ``routing_margins`` every
position is decided.

Every number compared goes into the result's line beside its limit
(``compared``) and onto the last lines of standard error.

The control (``control``) puts the reference, computed with int8 weights, in
the program's place: what the next precision below bf16 would read. A run of
the benchmark never calls it; ``chip_calls/pr29_control.py`` and the tests do.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

PROBE_TOKENS = 64
SAMPLE_TOKENS = 512  # served tokens a run compares, at the least
SAMPLE_REQUESTS = (3, 12)  # and the fewest and the most requests it takes them from
PAD_TO = 256  # reference sequences are padded to a multiple: few shapes to compile


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


def probe_prompt(seed: int, vocab: int) -> np.ndarray:
    return np.random.default_rng([seed, 0xC0FFEE]).integers(0, vocab, size=PROBE_TOKENS)


def program_probe(app, prompt: np.ndarray, vocab: int) -> np.ndarray:
    """The program's float32 logits ``(PROBE_TOKENS, vocab)`` of ``prompt``.
    Runs on the idle engine's pool: call it when nothing will be stepped again."""
    from nxdi_tpu.utils.accuracy import probe_all_logits

    lend_pool_to_probe(app)
    try:
        return probe_all_logits(app, prompt[None, :])[0][:, :vocab].astype(np.float32)
    finally:
        take_pool_back(app)


def sample_served(finished: Sequence, seed: int, tokens: int = SAMPLE_TOKENS) -> List:
    """Of the clean finished requests: the longest (prompt + served), then
    others in an order drawn from ``seed`` until ``tokens`` served tokens and
    the fewest requests are in it, or the most."""
    clean = [s for s in finished if s.output is not None and s.fault is None]
    if not clean:
        return []
    longest = max(clean, key=lambda s: (s.prompt_len + len(s.output.token_ids), -s.index))
    rest = [s for s in clean if s is not longest]
    out, have = [longest], len(longest.output.token_ids)
    for i in np.random.default_rng([seed, 0x5A3B1E]).permutation(len(rest)):
        if (have >= tokens and len(out) >= SAMPLE_REQUESTS[0]) or len(out) >= SAMPLE_REQUESTS[1]:
            break
        out.append(rest[i])
        have += len(rest[i].output.token_ids)
    return out


def padded(ids: Sequence[int]) -> np.ndarray:
    """``ids`` followed by zeros up to a multiple of ``PAD_TO``: attention is
    causal, so what follows a position does not reach it."""
    out = np.zeros(-(-len(ids) // PAD_TO) * PAD_TO, dtype=np.int32)
    out[: len(ids)] = ids
    return out


def served_rows(sample) -> tuple:
    """``(ids, first, tokens)``: the sequence the reference reads (prompt +
    all served tokens but the last), the position that predicted the first
    served token, and the served tokens."""
    tokens = np.asarray(sample.output.token_ids, dtype=np.int32)
    ids = np.concatenate([np.asarray(sample.prompt, dtype=np.int32), tokens[:-1]])
    return ids, len(sample.prompt) - 1, tokens


def reference_probe(reference: Callable, params, config: dict, prompt) -> np.ndarray:
    """The reference's float32 logits ``(PROBE_TOKENS, vocab)`` of the probe prompt."""
    import jax

    rows = reference(params, config, padded(prompt))[:PROBE_TOKENS, : config["vocab_size"]]
    return np.asarray(jax.device_get(rows), dtype=np.float32)


def reference_gaps(reference: Callable, params, config: dict, ids, first: int, tokens) -> np.ndarray:
    """At each position ``first + i``: the reference's largest logit less its
    logit of ``tokens[i]``. Reduced on the device; one row of numbers comes back."""
    import jax
    import jax.numpy as jnp

    rows = reference(params, config, padded(ids))[first: first + len(tokens)]
    at = jnp.take_along_axis(rows, jnp.asarray(tokens)[:, None], axis=1)[:, 0]
    return np.asarray(jax.device_get(rows.max(axis=-1) - at), dtype=np.float32)


def margins_of(routing_margins: Optional[Callable], params, config, ids, first: int, n: int):
    """The reference's router margins at the ``n`` positions from ``first``
    (inf where the reference has no router)."""
    if routing_margins is None:
        return np.full(n, np.inf, dtype=np.float32)
    import jax

    got = jax.device_get(routing_margins(params, config, padded(ids)))
    return np.asarray(got, dtype=np.float32)[first: first + n]


def int8_weights(params):
    """Every matrix of ``params`` rounded to int8 with one scale per output
    channel (its largest magnitude / 127) and brought back to its dtype, in
    place (the input is donated): weight-only int8, the step below bf16 that
    halves a decode step's weight stream. Vectors (norms, biases) stay."""
    import jax
    import jax.numpy as jnp

    from benchmark.serving_app import leaf_kind

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    # (in, out) matrices scale per column; the embedding (vocab, hidden), which
    # a tied head reads as (hidden, vocab), per row; None: not a matrix
    axes = [None if leaf_kind(path) != "weight" or leaf.ndim < 2
            else -1 if getattr(path[-1], "key", None) == "embed_tokens" else -2
            for path, leaf in flat]

    def roundtrip(leaves):
        out = []
        for axis, w in zip(axes, leaves):
            if axis is None:
                out.append(w)
                continue
            f = w.astype(jnp.float32)
            scale = jnp.maximum(jnp.abs(f).max(axis=axis, keepdims=True), 1e-30) / 127.0
            out.append((jnp.clip(jnp.round(f / scale), -127, 127) * scale).astype(w.dtype))
        return out

    leaves = jax.jit(roundtrip, donate_argnums=0)([leaf for _, leaf in flat])
    return jax.tree_util.tree_unflatten(treedef, leaves)


def control_tokens(reference: Callable, lower_params, config: dict, prompt, samples) -> dict:
    """What the lower precision would have served: the reference over
    ``lower_params`` (``int8_weights``) at the probe prompt (its logits) and at
    every served position of ``samples`` (the token it puts first)."""
    import jax

    firsts = []
    for s in samples:
        ids, first, tokens = served_rows(s)
        rows = reference(lower_params, config, padded(ids))[first: first + len(tokens)]
        firsts.append(np.asarray(jax.device_get(rows.argmax(axis=-1)), dtype=np.int32))
    return {"probe": reference_probe(reference, lower_params, config, prompt), "tokens": firsts}


def limits_of(bench: dict, routed: bool) -> Dict[str, float]:
    """The limit of every number compared, as the configuration states them."""
    out = {"probe_mse": float(bench["logit_mse_tolerance"]),
           "probe_diff": float(bench["logit_tolerance"]),
           "served_gap": float(bench["served_gap_tolerance"])}
    if routed:
        loose = float(bench["logit_tolerance_undecided"])
        out.update(probe_diff_undecided=loose, served_gap_undecided=loose,
                   undecided_share=float(bench["undecided_share_max"]))
    return out


def judge(bench: dict, margin: Optional[float], probe_sq, probe_diff, probe_margin, gaps,
          gap_margin) -> Dict[str, dict]:
    """``{name: {"value", "limit"}}`` from per-position numbers: the probe's
    mean squared and largest |difference| and the served gap at each position,
    with each position's router margin; a position whose margin lies under
    ``margin`` is undecided (None: no router, every position decided). A number
    with nothing to read is None."""
    routed = margin is not None
    under = (lambda m: m < margin) if routed else (lambda m: np.zeros(m.shape, bool))

    def worst(values, margins, undecided: bool):
        picked = values[under(margins) == undecided]
        return float(picked.max()) if picked.size else None

    decided = probe_sq[~under(probe_margin)]
    numbers = {"probe_mse": float(decided.mean()) if decided.size else None,
               "probe_diff": worst(probe_diff, probe_margin, False),
               "served_gap": worst(gaps, gap_margin, False)}
    if routed:
        every = under(np.concatenate([probe_margin, gap_margin]))
        numbers.update(
            probe_diff_undecided=worst(probe_diff, probe_margin, True),
            served_gap_undecided=worst(gaps, gap_margin, True),
            undecided_share=float(every.mean()) if every.size else None,
        )
    limits = limits_of(bench, routed)
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def passes(compared: Dict[str, dict]) -> bool:
    """Every number within its limit. The undecided positions may be none
    (nothing to hold); the decided ones have to be there."""
    for name, c in compared.items():
        if c["value"] is None:
            if name.endswith("_undecided"):
                continue
            return False
        if not np.isfinite(c["value"]) or c["value"] > c["limit"]:
            return False
    return True


def check(params, config: dict, reference: Callable, seed: int, probe_got: np.ndarray,
          samples: Sequence, say, routing_margins: Optional[Callable] = None,
          control: Optional[dict] = None) -> Dict[str, object]:
    """Compare what the program made (``probe_got`` from ``program_probe``, the
    served tokens of ``samples``) with the reference. With ``control`` (from
    ``control_tokens``) the lower precision's logits and tokens stand in the
    program's place, at the same prompts and positions. Returns ``{"ok",
    "compared", ...}``."""
    bench = config["benchmark"]
    margin = float(bench["routing_margin"]) if routing_margins is not None else None
    prompt = probe_prompt(seed, config["vocab_size"])
    if control is not None:
        probe_got = control["probe"]
    ref = reference_probe(reference, params, config, prompt)
    probe_diff = np.abs(probe_got - ref).max(axis=-1)
    probe_sq = ((probe_got - ref) ** 2).mean(axis=-1)
    probe_margin = margins_of(routing_margins, params, config, prompt, 0, PROBE_TOKENS)

    gaps, gap_margin, longest = [np.zeros(0, np.float32)], [np.zeros(0, np.float32)], 0
    for k, s in enumerate(samples):
        ids, first, tokens = served_rows(s)
        if control is not None:
            tokens = control["tokens"][k]
        gaps.append(reference_gaps(reference, params, config, ids, first, tokens))
        gap_margin.append(margins_of(routing_margins, params, config, ids, first, len(tokens)))
        longest = max(longest, len(ids) + 1)
    gaps, gap_margin = np.concatenate(gaps), np.concatenate(gap_margin)

    compared = judge(bench, margin, probe_sq, probe_diff, probe_margin, gaps, gap_margin)
    ok = passes(compared)
    undecided = 0 if margin is None else int((np.concatenate([probe_margin, gap_margin]) < margin).sum())
    what = "correctness (CONTROL: int8 weights in the program's place)" if control else "correctness"
    say(f"{what}: {len(samples)} finished requests sampled, {gaps.size} served tokens, longest sequence "
        f"{longest}; served-token gap to the reference's top logit: widest {gaps.max() if gaps.size else None}, "
        f"mean {gaps.mean() if gaps.size else None}; probe over {PROBE_TOKENS} positions: max |diff| "
        f"{probe_diff.max():.4f}, rms {np.sqrt(probe_sq.mean()):.5f} (reference logit std "
        f"{ref.std():.3f}); positions decided "
        f"{probe_diff.size + gaps.size - undecided}, undecided {undecided}; {'ok' if ok else 'FAILED'}")
    for name, c in compared.items():
        say(f"  compared {name} = {c['value']} (limit {c['limit']})")
    return {"ok": ok, "compared": compared, "served_gap_mean": float(gaps.mean()) if gaps.size else None,
            "served_tokens": int(gaps.size), "reference_logit_std": float(ref.std())}
