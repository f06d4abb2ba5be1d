"""Radix prefix cache, engine edition (ISSUE 13 correctness anchor): greedy
outputs must be TOKEN-IDENTICAL with the cache ON vs OFF under shared
prompts, interleaved arrivals, forced preemption, chunked prefill, and
mixed dispatch — while the cache actually hits (tokens_saved > 0). Plus
the ``n > 1`` continuation fork: greedy parity with n independent runs,
and device-level copy-on-write isolation of the shared partial block."""

import numpy as np
import pytest

from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
from nxdi_tpu.models.llama import modeling_llama as llama
from nxdi_tpu.runtime.application import TpuModelForCausalLM
from nxdi_tpu.serving import InferenceEngine, SamplingParams, SchedulerConfig

SHARED = [5, 9, 3, 17, 2, 8, 11, 42, 7, 13]  # > 1 full block at pa_block_size=8
PROMPTS = [
    SHARED + [21, 4],
    SHARED + [33, 6],
    SHARED + [21, 4, 9],  # extends prompt 0 — deeper radix path
]


def _build_app(hf_model, hf_cfg, **tcfg_kwargs):
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    defaults = dict(
        tp_degree=1,
        seq_len=64,
        max_context_length=32,
        batch_size=2,
        dtype="float32",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True,
        telemetry="basic",
    )
    defaults.update(tcfg_kwargs)
    cfg = llama.LlamaInferenceConfig(
        TpuConfig(**defaults), load_config=lambda: hf_cfg.to_dict()
    )

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", cfg, model_family=llama)
    app.load()
    return app


def _paged_engine(hf_model, hf_cfg, cache_on, *, num_slots=3, app_kw=None,
                  sched_kw=None):
    kw = dict(
        is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=32,
        ctx_batch_size=1, tkg_batch_size=3,
        is_prefix_caching=cache_on,
    )
    kw.update(app_kw or {})
    app = _build_app(hf_model, hf_cfg, **kw)
    skw = dict(num_slots=num_slots, prefix_cache=cache_on)
    skw.update(sched_kw or {})
    return app, InferenceEngine(app, SchedulerConfig(**skw))


def _sequential_waves(engine, prompts, max_new=8):
    """Wave 1 seeds the cache (retire inserts); later arrivals must hit."""
    reqs = [engine.add_request(prompts[0], SamplingParams(max_new_tokens=max_new))]
    outs = engine.run()
    for p in prompts[1:]:
        reqs.append(engine.add_request(p, SamplingParams(max_new_tokens=max_new)))
    outs += engine.run()
    got = {o.request_id: o.token_ids for o in outs}
    return [got[r.request_id] for r in reqs]


def test_prefix_cache_parity_and_hits(tiny_hf_llama):
    """The headline anchor: ON == OFF token streams, with real hits, real
    tokens saved, and the admission's cached/total in the flight records."""
    hf_model, hf_cfg = tiny_hf_llama
    _, eng_off = _paged_engine(hf_model, hf_cfg, cache_on=False)
    off = _sequential_waves(eng_off, PROMPTS)

    app, eng_on = _paged_engine(hf_model, hf_cfg, cache_on=True)
    on = _sequential_waves(eng_on, PROMPTS)
    assert on == off

    pc = eng_on.scheduler.prefix_cache
    assert pc.hits_n >= 2, "wave-2 arrivals share a full block and must hit"
    assert pc.tokens_saved_n > 0
    assert pc.hit_rate_pct > 0
    # cached tokens surfaced per-admission in the flight recorder
    admitted = [
        a for r in eng_on.flight.snapshot_records() for a in r.admitted
    ]
    assert any(a["cached"] > 0 for a in admitted)
    assert all(a["total"] >= a["cached"] for a in admitted)
    # engine-level state block mirrors the same counters
    st = eng_on.scheduler_state()["prefix_cache"]
    assert st["hits"] == pc.hits_n and st["tokens_saved"] == pc.tokens_saved_n
    # registry counters carried the same story (scrape surface)
    assert app.telemetry.registry.get("nxdi_prefix_hits").value() == pc.hits_n

    # flightrec timeline renders the cached=K/N column without blowing up
    from nxdi_tpu.cli.flightrec import _print_timeline

    _print_timeline([r.to_dict() for r in eng_on.flight.snapshot_records()], 50)


def test_prefix_cache_parity_interleaved_arrivals(tiny_hf_llama):
    """Cache-ON engine with requests landing mid-flight (the classic
    interleaved pattern): identical streams to cache OFF."""
    hf_model, hf_cfg = tiny_hf_llama

    def run(cache_on):
        _, eng = _paged_engine(hf_model, hf_cfg, cache_on)
        reqs = [eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=10))]
        outs = eng.run()  # retire seeds the cache
        reqs.append(eng.add_request(PROMPTS[1], SamplingParams(max_new_tokens=12)))
        outs += eng.step() + eng.step()
        # third request arrives while the second decodes
        reqs.append(eng.add_request(PROMPTS[2], SamplingParams(max_new_tokens=9)))
        outs += eng.run()
        got = {o.request_id: o.token_ids for o in outs}
        return [got[r.request_id] for r in reqs], eng

    off, _ = run(False)
    on, eng = run(True)
    assert on == off
    assert eng.scheduler.prefix_cache.hits_n >= 2


def test_prefix_cache_parity_across_preemption(tiny_hf_llama):
    """Preemption-free inserts the victim's blocks, so its recompute resume
    re-matches its own chain — and stays token-identical to cache OFF."""
    hf_model, hf_cfg = tiny_hf_llama

    def run(cache_on):
        _, eng = _paged_engine(
            hf_model, hf_cfg, cache_on,
            num_slots=2,
            app_kw=dict(pa_num_blocks=16, tkg_batch_size=2),
            sched_kw=dict(watermark_blocks=1),
        )
        ra = eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=10))
        rb = eng.add_request(PROMPTS[1], SamplingParams(max_new_tokens=10))
        outs = eng.step() + eng.step()
        victim = eng.preempt_youngest()
        assert victim is not None and victim.preemptions == 1
        outs += eng.run()
        got = {o.request_id: o.token_ids for o in outs}
        return [got[ra.request_id], got[rb.request_id]], eng

    off, _ = run(False)
    on, eng = run(True)
    assert on == off
    pc = eng.scheduler.prefix_cache
    # the victim's resume must have matched the chain its preemption parked
    assert pc.hits_n >= 1 and pc.tokens_saved_n > 0


def test_prefix_cache_parity_chunked_prefill(tiny_hf_llama):
    """Chunked prefill sees the cache as a shorter prompt: the uncached tail
    still chunks, streams stay exact, and the repeat prompt spends fewer
    prefill chunks than its first service."""
    hf_model, hf_cfg = tiny_hf_llama
    from nxdi_tpu.runtime.application import TAG_PREFIX_PREFILL

    rng = np.random.default_rng(0)
    long_prompt = rng.integers(1, 255, size=20).tolist()  # 2 full pa blocks + tail

    def chunks_dispatched(app):
        disp = app.telemetry.dispatches_total
        return sum(
            v for k, v in disp.series().items()
            if k[disp.label_names.index("submodel")] == TAG_PREFIX_PREFILL
        )

    def run(cache_on):
        app, eng = _paged_engine(
            hf_model, hf_cfg, cache_on,
            app_kw=dict(
                chunked_prefill_config={"chunk_size": 8, "kernel_q_tile_size": 8},
                pa_block_size=8,
            ),
        )
        r1 = eng.add_request(long_prompt, SamplingParams(max_new_tokens=6))
        outs = eng.run()
        before_repeat = chunks_dispatched(app)
        r2 = eng.add_request(long_prompt, SamplingParams(max_new_tokens=6))
        outs += eng.run()
        got = {o.request_id: o.token_ids for o in outs}
        repeat_chunks = chunks_dispatched(app) - before_repeat
        return [got[r1.request_id], got[r2.request_id]], repeat_chunks, eng

    off, off_chunks, _ = run(False)
    on, on_chunks, eng = run(True)
    assert on == off
    assert on[0] == on[1]  # same prompt, greedy — identical continuation
    assert eng.scheduler.prefix_cache.hits_n >= 1
    # 16 of 20 tokens rode the cache: the repeat tail fits ONE chunk where
    # the cold run needed several dispatches
    assert on_chunks < off_chunks


def test_prefix_cache_parity_mixed_dispatch(tiny_hf_llama):
    """Mixed packed dispatch path: cache-ON streams equal cache-OFF, with
    the second wave's prefill tokens packing only the uncached tail."""
    hf_model, hf_cfg = tiny_hf_llama

    def run(cache_on):
        _, eng = _paged_engine(
            hf_model, hf_cfg, cache_on,
            app_kw=dict(mixed_dispatch=True),
        )
        return _sequential_waves(eng, PROMPTS), eng

    off, _ = run(False)
    on, eng = run(True)
    assert on == off
    assert eng.scheduler.prefix_cache.hits_n >= 2


def test_n_fork_greedy_parity(tiny_hf_llama):
    """SamplingParams(n=2): both continuations equal the solo greedy run;
    outputs carry parent_request_id; COW fired on the shared partial
    block (prompt length 12 leaves positions 8..10 shared in block 1)."""
    hf_model, hf_cfg = tiny_hf_llama
    _, solo_eng = _paged_engine(hf_model, hf_cfg, cache_on=True)
    solo = solo_eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=8))
    (solo_out,) = solo_eng.run()

    _, eng = _paged_engine(hf_model, hf_cfg, cache_on=True)
    prim = eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=8, n=2))
    outs = eng.run()
    assert len(outs) == 2
    assert all(o.token_ids == solo_out.token_ids for o in outs)
    by_id = {o.request_id: o for o in outs}
    assert prim.request_id in by_id
    sib = next(o for o in outs if o.request_id != prim.request_id)
    assert sib.metrics["parent_request_id"] == prim.request_id
    pc = eng.scheduler.prefix_cache
    assert pc.cow_copies_n >= 1, "partial boundary block write must COW"


def test_n_fork_cow_isolation_device_level(tiny_hf_llama):
    """The isolation anchor, at the KV bytes: after an n=2 fork runs out,
    - the FULL shared block is the same physical block in both tables and
      its contents never changed from the parent's prefill,
    - the partial boundary block diverged into two physical blocks (COW),
    - the shared positions inside the boundary block are bit-identical
      across both copies (the copy preserved the prefix KV)."""
    import jax

    hf_model, hf_cfg = tiny_hf_llama
    app, eng = _paged_engine(hf_model, hf_cfg, cache_on=True)
    bs = 8
    prompt = PROMPTS[0]  # 12 tokens: block 0 full, block 1 holds pos 8..11
    prim = eng.add_request(prompt, SamplingParams(max_new_tokens=6, n=2))

    # step until both sequences are live, tracking the final table each
    # held (COW may swap boundary entries at any step; tables vanish on
    # retirement, so capture every step)
    mgr = eng.scheduler.block_manager
    outs, seen = [], {}
    shared_full = None
    k_snap = None
    for _ in range(40):
        outs += eng.step()
        for sid, tab in mgr._tables.items():
            seen[sid] = list(tab)
        if shared_full is None and len(seen) == 2:
            pt, st = (seen[k] for k in sorted(seen))
            if pt and st and pt[0] == st[0]:
                shared_full = pt[0]
                k_snap = np.asarray(jax.device_get(eng.app.kv_cache["k"]))
        if len(outs) == 2:
            break
    assert len(outs) == 2
    assert len(seen) == 2, "sibling never admitted"
    assert shared_full is not None, "full prompt block was never shared"
    pc = eng.scheduler.prefix_cache
    assert pc.cow_copies_n >= 1

    ptab, stab = seen[prim.request_id], next(
        t for k, t in seen.items() if k != prim.request_id
    )
    # the fork was real (one physical full block)...
    assert ptab[0] == stab[0] == shared_full
    # ...and the partial boundary block diverged into private copies
    assert ptab[1] != stab[1], "boundary block must copy-on-write, not alias"

    k_after = np.asarray(jax.device_get(eng.app.kv_cache["k"]))
    # (1) the full shared block's KV never changed after the fork point
    sl = slice(shared_full * bs, (shared_full + 1) * bs)
    np.testing.assert_array_equal(k_after[:, sl], k_snap[:, sl])
    # (2) the COW preserved the shared prefix: positions 8..10 (offsets
    # 0..2 of the boundary block) are bit-identical across both copies
    p1, s1 = ptab[1], stab[1]
    np.testing.assert_array_equal(
        k_after[:, p1 * bs : p1 * bs + 3], k_after[:, s1 * bs : s1 * bs + 3]
    )
    assert np.any(k_after[:, p1 * bs : p1 * bs + 3]), "prefix KV is all zero"


def test_n_fork_unpaged_falls_back_to_prefill(tiny_hf_llama):
    """n=2 on the contiguous layout (no paged pool, no fork): siblings just
    prefill independently — outputs still correct and grouped."""
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg,
        is_continuous_batching=True, ctx_batch_size=2, tkg_batch_size=2,
        kv_cache_batch_size=2,
    )
    eng = InferenceEngine(app, SchedulerConfig(num_slots=2))
    prim = eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=6, n=2))
    outs = eng.run()
    assert len(outs) == 2
    assert outs[0].token_ids == outs[1].token_ids
    sib = next(o for o in outs if o.request_id != prim.request_id)
    assert sib.metrics["parent_request_id"] == prim.request_id


def test_prefix_cache_requires_paged_layout(tiny_hf_llama):
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg,
        is_continuous_batching=True, ctx_batch_size=2, tkg_batch_size=2,
        kv_cache_batch_size=2,
    )
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(app, SchedulerConfig(num_slots=2, prefix_cache=True))


def test_a_follower_of_a_chain_cached_past_an_overrun_gets_the_same_tokens(tiny_hf_llama):
    """ISSUE 33: a request that ends on an EOS the host could not foresee has
    one more decode in flight; that step wrote KV at the position after its
    last token, in its own block, and only its COMMITTED positions entered the
    radix tree at retirement. A follower whose prompt is the finished
    sequence and more forks that chain and must produce what it produces on
    an engine without the cache."""
    hf_model, hf_cfg = tiny_hf_llama

    def run(cache_on):
        # both apps compile the prefix-prefill program (one decode window); the
        # radix tree is on in one engine only
        app, eng = _paged_engine(
            hf_model, hf_cfg, cache_on,
            app_kw=dict(pa_block_size=4, pa_num_blocks=48, is_prefix_caching=True,
                        max_context_length=48),
        )
        free = eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=14))
        toks = eng.run()[0].token_ids
        # an EOS after the 9th new token or later: >= 5 full blocks of 4 committed
        j = next(j for j in range(8, len(toks)) if toks[j] not in toks[:j])
        eng2 = InferenceEngine(
            app, SchedulerConfig(num_slots=3, prefix_cache=cache_on)
        )
        other = eng2.add_request(PROMPTS[1], SamplingParams(max_new_tokens=20))
        first = eng2.add_request(
            PROMPTS[0], SamplingParams(max_new_tokens=14, eos_token_ids=(toks[j],))
        )
        outs = []
        while first.state != "FINISHED":
            outs += eng2.step()
        # learnt of the EOS with the row dispatched once more
        assert any(r is first for _, r, _ in eng2._inflight.rows)
        follower = eng2.add_request(
            PROMPTS[0] + first.generated + [9, 4, 21], SamplingParams(max_new_tokens=8)
        )
        outs += eng2.run()
        got = {o.request_id: o for o in outs}
        assert got[first.request_id].finish_reason == "eos"
        assert got[first.request_id].token_ids == toks[: j + 1]
        assert free.request_id != first.request_id
        return ([got[r.request_id].token_ids for r in (other, first, follower)], eng2, app)

    off, _, _ = run(False)
    on, eng, app = run(True)
    assert on == off
    pc = eng.scheduler.prefix_cache
    assert pc.hits_n >= 1 and pc.tokens_saved_n >= 20, "the follower forked the finished chain"
    assert app.telemetry.registry.get("nxdi_decode_overrun_tokens_total").value() == 1
    assert eng.block_manager.num_free_blocks() == 48  # cached blocks are reclaimable
