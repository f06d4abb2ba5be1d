"""Continuous-batching serving engine (nxdi_tpu/serving) — correctness anchor:
greedy engine outputs must be TOKEN-IDENTICAL to per-prompt static
``generate``, on an interleaved-arrival workload, with and without forced
preemption, across paged and contiguous layouts, chunked prefill, multistep
decode windows, and slot recycling.

Also the tier-1 serving smoke: the ``python -m nxdi_tpu.cli.serve`` demo
(tiny llama, 8 requests, forced preemption) must complete and export the
serving gauges/counters with non-trivial values."""

import numpy as np
import pytest

from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
from nxdi_tpu.models.llama import modeling_llama as llama
from nxdi_tpu.runtime.application import TpuModelForCausalLM
from nxdi_tpu.runtime.model_wrapper import (
    TAG_TOKEN_GENERATION,
    TAG_TOKEN_GENERATION_MULTISTEP,
)
from nxdi_tpu.serving import (
    InferenceEngine,
    SamplingParams,
    SchedulerConfig,
)
from nxdi_tpu.utils.accuracy import hf_greedy_generate as hf_greedy

P0 = [5, 9, 3, 17, 2, 8, 11, 42]
P1 = [7, 13, 21, 4, 33]
P2 = [9, 9, 2, 40, 17, 3]


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


def _expected(hf_model, prompt, n):
    return hf_greedy(hf_model, np.array([prompt]), n)[0, len(prompt):].tolist()


def test_engine_paged_parity_interleaved_vs_static_generate(tiny_hf_llama):
    """Interleaved arrivals on the paged app: every request's stream must be
    token-identical to the per-prompt STATIC generate (the plain adapter on
    a non-paged app from the same weights) — the acceptance anchor."""
    from nxdi_tpu.generation.hf_adapter import HuggingFaceGenerationAdapter

    hf_model, hf_cfg = tiny_hf_llama
    static = HuggingFaceGenerationAdapter(
        _build_app(hf_model, hf_cfg, ctx_batch_size=1, tkg_batch_size=1,
                   batch_size=1)
    )

    app = _build_app(
        hf_model, hf_cfg,
        is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=32,
        ctx_batch_size=1, tkg_batch_size=3,
    )
    engine = InferenceEngine(app, SchedulerConfig(num_slots=3))

    streams = {}

    def cb(r, tok):
        streams.setdefault(r.request_id, []).append(tok)

    budgets = {0: 10, 1: 12, 2: 9}
    reqs = {}
    reqs[0] = engine.add_request(P0, SamplingParams(max_new_tokens=10), on_token=cb)
    reqs[1] = engine.add_request(P1, SamplingParams(max_new_tokens=12), on_token=cb)
    outs = engine.step() + engine.step()
    # request 2 arrives mid-flight — its prefill must not disturb rows 0/1
    reqs[2] = engine.add_request(P2, SamplingParams(max_new_tokens=9), on_token=cb)
    outs += engine.run()

    got = {o.request_id: o.token_ids for o in outs}
    assert len(got) == 3
    for i, prompt in enumerate((P0, P1, P2)):
        full = static.generate(
            np.array([prompt], dtype=np.int64), max_new_tokens=budgets[i]
        )
        expected = full[0, len(prompt):].tolist()
        assert got[reqs[i].request_id] == expected
        # streaming callbacks saw the same tokens in the same order
        assert streams[reqs[i].request_id] == expected
    # no request was preempted in this sizing
    assert all(o.metrics["preemptions"] == 0 for o in outs)

    # intake validation: over-long prompts fail fast, budgets clamp
    with pytest.raises(ValueError, match="max_context_length"):
        engine.add_request(list(range(1, 40)))
    with pytest.raises(ValueError, match="decode room"):
        engine.add_request(list(range(1, 70)))
    # duplicate LIVE ids would share one block table (silent KV corruption)
    engine.add_request(P0, SamplingParams(max_new_tokens=2), request_id=777)
    with pytest.raises(ValueError, match="already live"):
        engine.add_request(P1, SamplingParams(max_new_tokens=2), request_id=777)
    # the auto counter catching up to a live user-chosen id redraws instead
    # of spuriously rejecting a caller who never picked an id
    import itertools

    from nxdi_tpu.serving import Request

    Request._ids = itertools.chain([777], Request._ids)
    auto = engine.add_request(P2, SamplingParams(max_new_tokens=2))
    assert auto.request_id != 777
    engine.run()  # finished ids may be reused
    engine.add_request(P1, SamplingParams(max_new_tokens=2), request_id=777)
    engine.run()


def test_engine_parity_across_preemption(tiny_hf_llama):
    """Forced AND natural (pool-exhaustion) preemption: the victim resumes
    by re-prefilling prompt+generated and its final stream stays identical
    to the uninterrupted greedy run."""
    hf_model, hf_cfg = tiny_hf_llama

    # forced: evict the youngest after one step, mid-generation
    app = _build_app(
        hf_model, hf_cfg,
        is_block_kv_layout=True, pa_block_size=4, pa_num_blocks=16,
        ctx_batch_size=1, tkg_batch_size=2,
    )
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2, watermark_blocks=1))
    ra = engine.add_request(P0, SamplingParams(max_new_tokens=10))
    rb = engine.add_request(P1, SamplingParams(max_new_tokens=10))
    outs = engine.step()
    victim = engine.preempt_youngest()
    assert victim is not None and victim.preemptions == 1
    outs += engine.run()
    got = {o.request_id: o.token_ids for o in outs}
    assert got[ra.request_id] == _expected(hf_model, P0, 10)
    assert got[rb.request_id] == _expected(hf_model, P1, 10)
    assert app.telemetry.serve_preemptions_total.value() >= 1

    # natural: a pool too small for both full sequences forces an eviction
    app2 = _build_app(
        hf_model, hf_cfg,
        is_block_kv_layout=True, pa_block_size=4, pa_num_blocks=8,
        ctx_batch_size=1, tkg_batch_size=2,
    )
    engine2 = InferenceEngine(
        app2, SchedulerConfig(num_slots=2, watermark_blocks=1)
    )
    rc = engine2.add_request(P0, SamplingParams(max_new_tokens=12))
    rd = engine2.add_request(P1, SamplingParams(max_new_tokens=12))
    outs2 = engine2.run()
    got2 = {o.request_id: o.token_ids for o in outs2}
    assert got2[rc.request_id] == _expected(hf_model, P0, 12)
    assert got2[rd.request_id] == _expected(hf_model, P1, 12)
    assert app2.telemetry.serve_preemptions_total.value() >= 1, (
        "the sizing was chosen to exhaust the pool mid-decode"
    )


def test_engine_unresumable_preemption_fails_only_that_request(tiny_hf_llama):
    """A preempted request whose prompt+generated replay outgrew
    max_context_length (no prefix/chunked submodel compiled) must fail as
    finish_reason="error" WITHOUT crashing the engine — its neighbor keeps
    serving to a correct completion."""
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg,
        is_block_kv_layout=True, pa_block_size=4, pa_num_blocks=32,
        max_context_length=16,
        ctx_batch_size=1, tkg_batch_size=2,
    )
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
    survivor = engine.add_request(P1, SamplingParams(max_new_tokens=10))
    doomed = engine.add_request(P0, SamplingParams(max_new_tokens=20))
    outs = engine.step()
    # decode until the doomed request's replay would exceed max_ctx (16)
    while doomed.total_len <= 16:
        outs += engine.step()
    assert engine.scheduler.preempt_youngest() is doomed
    outs += engine.run()
    got = {o.request_id: o for o in outs}
    assert got[doomed.request_id].finish_reason == "error"
    assert got[survivor.request_id].finish_reason == "length"
    assert got[survivor.request_id].token_ids == _expected(hf_model, P1, 10)


def test_engine_multistep_windows_eos_and_tail_fallback(tiny_hf_llama):
    """Contiguous engine with decode_steps_per_dispatch=4:

    - bulk decode rides tkg_multistep windows (parity with greedy),
    - a request within K tokens of its budget falls back to 1-step TKG
      dispatches (never overshoots max_new_tokens),
    - an EOS INSIDE a window finishes the row exactly there (in-scan
      masking pads the tail; the engine discards it)."""
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg,
        is_continuous_batching=True, ctx_batch_size=2, tkg_batch_size=2,
        kv_cache_batch_size=2, decode_steps_per_dispatch=4,
    )
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
    # max_new=6: CTE token, then remaining 5 -> one 4-window, then remaining
    # 1 -> a single-step dispatch (the tail fallback under test)
    ra = engine.add_request(P0, SamplingParams(max_new_tokens=6))
    outs = engine.run()
    assert outs[0].token_ids == _expected(hf_model, P0, 6)
    disp = app.telemetry.dispatches_total
    assert disp.value(submodel=TAG_TOKEN_GENERATION_MULTISTEP, bucket="64",
                      steps="4") >= 1
    assert disp.value(submodel=TAG_TOKEN_GENERATION, bucket="64",
                      steps="1") >= 1, "tail within K must dispatch 1-step"

    # EOS mid-window: golden token g2 becomes the eos id; the engine must
    # stop row exactly at g2 even though the window ran 4 in-scan steps
    expected = _expected(hf_model, P0, 12)
    eos = expected[2]
    assert eos not in expected[:2]
    rb = engine.add_request(
        P0, SamplingParams(max_new_tokens=12, eos_token_ids=(eos,))
    )
    outs2 = engine.run()
    assert outs2[0].finish_reason == "eos"
    assert outs2[0].token_ids == expected[:3]


def test_engine_dirty_slot_recycling(tiny_hf_llama):
    """One slot serving three requests back to back: each new admission
    overwrites the previous occupant's KV from position 0, so a dirty slot
    (and dirty pool blocks) can never leak into the next request."""
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg,
        is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=16,
        ctx_batch_size=1, tkg_batch_size=1, batch_size=1,
    )
    engine = InferenceEngine(app, SchedulerConfig(num_slots=1))
    for prompt, n in ((P0, 10), (P1, 7), (P2, 9)):
        req = engine.add_request(prompt, SamplingParams(max_new_tokens=n))
        (out,) = engine.run()
        assert out.request_id == req.request_id
        assert out.token_ids == _expected(hf_model, prompt, n)
        assert req.slot is None and engine.scheduler.slots_busy == 0


def test_engine_chunked_prefill_admission(tiny_hf_llama):
    """chunked_prefill_config: a long prompt prefills chunk-by-chunk across
    engine steps (CTE then prefix-prefill dispatches) while a short
    neighbor decodes in between — both streams stay exact."""
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg,
        is_block_kv_layout=True,
        chunked_prefill_config={"chunk_size": 8, "kernel_q_tile_size": 8},
        pa_block_size=4, pa_num_blocks=32,
        ctx_batch_size=1, tkg_batch_size=2,
    )
    from nxdi_tpu.runtime.application import TAG_PREFIX_PREFILL

    rng = np.random.default_rng(0)
    long_prompt = rng.integers(1, 255, size=20).tolist()  # 3 chunks of 8
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
    short = engine.add_request(P1, SamplingParams(max_new_tokens=8))
    longr = engine.add_request(long_prompt, SamplingParams(max_new_tokens=6))
    outs = engine.run()
    got = {o.request_id: o.token_ids for o in outs}
    assert got[short.request_id] == _expected(hf_model, P1, 8)
    assert got[longr.request_id] == _expected(hf_model, long_prompt, 6)
    disp = app.telemetry.dispatches_total
    chunks = sum(
        v for k, v in disp.series().items()
        if k[disp.label_names.index("submodel")] == TAG_PREFIX_PREFILL
    )
    assert chunks >= 2, "the 20-token prompt must continue through 2+ chunks"


class _TickClock:
    """An injected telemetry clock: every reading is 1 ms after the last,
    so each phase, and each stretch under no phase, has a time of its own."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        self.t += 1e-3
        return self.t


_PHASE_PATHS = {
    # the path the benchmark's cells run: paged KV, one prefill row, 1-step decode
    "split": dict(is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=32,
                  ctx_batch_size=1, tkg_batch_size=2),
    "mixed": dict(is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=32,
                  ctx_batch_size=1, tkg_batch_size=2, mixed_dispatch=True),
    "multistep": dict(is_continuous_batching=True, ctx_batch_size=2, tkg_batch_size=2,
                      kv_cache_batch_size=2, decode_steps_per_dispatch=4),
    "device_loop": dict(is_continuous_batching=True, ctx_batch_size=2, tkg_batch_size=2,
                        kv_cache_batch_size=2, device_loop=True, device_loop_fence=3),
}


@pytest.mark.parametrize("path", sorted(_PHASE_PATHS))
def test_engine_step_phases_cover_the_step(tiny_hf_llama, path):
    """Every StepRecord's phases are exhaustive and non-overlapping under an
    injected clock: sum(phases) + other_s == wall_s with other_s >= 0, a
    decode-only step enters schedule, pack, pad, enqueue, fetch and emit,
    host_s is the wall less the wait for the device's tokens, and the phase
    histogram holds what the records hold."""
    from nxdi_tpu.telemetry import PHASES

    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(hf_model, hf_cfg, **_PHASE_PATHS[path])
    app.telemetry.clock = _TickClock()
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
    engine.add_request(P0, SamplingParams(max_new_tokens=12))
    outs = engine.step()
    engine.add_request(P1, SamplingParams(max_new_tokens=10))  # a prefill joins a decode
    outs += engine.run()
    assert len(outs) == 2 and all(o.finish_reason == "length" for o in outs)

    records = engine.flight.snapshot_records()
    assert [r.step for r in records] == list(range(len(records)))
    decode_only = [r for r in records if (r.decode or r.mixed) and not r.prefills]
    assert decode_only and any(r.prefills for r in records)
    for r in records:
        assert set(r.phases) <= set(PHASES)
        assert sum(r.phases.values()) + r.other_s == pytest.approx(r.wall_s)
        assert r.other_s > -1e-9  # no instant counted under two phases
        assert r.host_s == pytest.approx(r.wall_s - r.phases["fetch"])
        assert r.open_phase is None
        d = r.to_dict()
        assert d["phases"] == r.phases and d["other_s"] == pytest.approx(r.other_s)
    for r in decode_only:
        assert {"schedule", "pack", "pad", "enqueue", "fetch", "emit"} <= set(r.phases)
    if path in ("split", "mixed"):
        assert all("kv" in r.phases for r in decode_only)  # the paged pool grows rows
    hist = app.telemetry.registry.get("nxdi_engine_phase_seconds")
    for name in PHASES:
        series = hist.snapshot_series(phase=name)
        assert series.sum == pytest.approx(sum(r.phases.get(name, 0.0) for r in records))
        assert series.count == sum(1 for r in records if name in r.phases)
    host = app.telemetry.registry.get("nxdi_engine_host_seconds").snapshot_series()
    assert host.sum == pytest.approx(sum(r.host_s for r in records))


def test_engine_and_wrappers_make_no_annotation_when_telemetry_is_off(
    tiny_hf_llama, monkeypatch
):
    """``TpuConfig(telemetry={"detail": "off"})``: the engine and the
    wrappers create no TraceAnnotation and record no phase."""
    import nxdi_tpu.telemetry as telemetry

    made = []
    monkeypatch.setattr(telemetry, "TraceAnnotation", lambda *a, **k: made.append(a))
    monkeypatch.setattr(telemetry, "StepTraceAnnotation", lambda *a, **k: made.append(a))
    hf_model, hf_cfg = tiny_hf_llama
    app = _build_app(
        hf_model, hf_cfg, telemetry={"detail": "off"},
        **_PHASE_PATHS["split"],
    )
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
    engine.add_request(P0, SamplingParams(max_new_tokens=4))
    outs = engine.run()
    assert outs[0].token_ids == _expected(hf_model, P0, 4)
    assert made == [] and engine.flight is None
    assert app.telemetry.registry.get("nxdi_engine_phase_seconds").series() == {}


def test_serve_cli_demo_tier1_smoke(capsys):
    """Tier-1 serving smoke: the cli.serve demo (tiny llama, 8 Poisson
    requests, forced preemption) completes and its exported Prometheus text
    carries the serving gauges/counters with non-trivial values."""
    from nxdi_tpu.cli.serve import main

    rc = main([
        "--requests", "8",
        "--rate", "200",
        "--max-new-tokens", "5",
        "--slots", "3",
        "--pa-num-blocks", "24",
        "--seed", "0",
        "--format", "prom",
        "-q",
    ])
    assert rc == 0
    prom = capsys.readouterr().out
    # the peak-occupancy capture must show the engine under load
    metrics = {}
    for line in prom.splitlines():
        if line.startswith("nxdi_serve_"):
            name, val = line.rsplit(" ", 1)
            metrics[name] = float(val)
    assert metrics["nxdi_serve_preemptions_total"] >= 1
    assert metrics["nxdi_serve_slots_busy"] >= 1
    assert metrics["nxdi_serve_queue_depth"] >= 1


def test_serve_cli_demo_mixed_dispatch_smoke(capsys):
    """Tier-1 serving smoke, mixed edition: the same cli.serve demo with
    --mixed-dispatch completes, and the exported Prometheus text shows the
    packed program carried the traffic (mixed packing gauges populated)."""
    from nxdi_tpu.cli.serve import main

    rc = main([
        "--requests", "8",
        "--rate", "200",
        "--max-new-tokens", "5",
        "--slots", "3",
        "--pa-num-blocks", "24",
        "--mixed-dispatch",
        "--seed", "0",
        "--format", "prom",
        "-q",
    ])
    assert rc == 0
    prom = capsys.readouterr().out
    assert 'nxdi_dispatches_total{submodel="mixed_model"' in prom
    packed = [
        line for line in prom.splitlines()
        if line.startswith("nxdi_mixed_packed_tokens")
    ]
    assert packed, "mixed packing gauges missing from the export"
    assert any(float(line.rsplit(" ", 1)[1]) > 0 for line in packed), (
        "no bucket rung ever saw packed tokens"
    )
    # the packed program really carried dispatches
    assert 'submodel="mixed_model"' in prom
