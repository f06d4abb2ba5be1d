"""The plain decode step's two halves in their two orders (ISSUE 33): an engine
that dispatches step N+1 before it collects step N must serve every stream
token for token as one that collects first. The synchronous engine is the
SAME code held to the other order by a condition the engine observes (a post
hook on the token-generation wrapper: something reads each dispatch's outputs
on the host), not by a switch."""

import numpy as np
import pytest

from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
from nxdi_tpu.models.llama import modeling_llama as llama
from nxdi_tpu.runtime.application import TpuModelForCausalLM
from nxdi_tpu.runtime.model_wrapper import TAG_TOKEN_GENERATION, decode_next_ids
from nxdi_tpu.serving import InferenceEngine, SamplingParams, SchedulerConfig

PROMPTS = [
    [5, 9, 3, 17, 2, 8, 11, 42],
    [7, 13, 21, 4, 33],
    [9, 9, 2, 40, 17, 3],
    [31, 2, 77, 5],
    [12, 100, 6, 6, 19, 23, 8],
]


def build_app(hf_model, hf_cfg, **tcfg_kwargs):
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    defaults = dict(
        tp_degree=1, seq_len=64, max_context_length=32, batch_size=2, dtype="float32",
        on_device_sampling_config=OnDeviceSamplingConfig(), skip_warmup=True,
        telemetry="basic", is_block_kv_layout=True, pa_block_size=4, pa_num_blocks=48,
        ctx_batch_size=1, tkg_batch_size=3,
    )
    defaults.update(tcfg_kwargs)
    cfg = llama.LlamaInferenceConfig(TpuConfig(**defaults), load_config=lambda: hf_cfg.to_dict())

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", cfg, model_family=llama)
    app.load()
    return app


def collect_first(app):
    """Hold ``app``'s engines to the synchronous order: a post hook reads every
    dispatch's outputs on the host, which the engine sees (``_may_chain``)."""
    app.models[TAG_TOKEN_GENERATION].post_hooks.append(lambda tag: None)


def chained_steps(engine):
    return [r for r in engine.flight.snapshot_records() if r.chained]


def serve(engine, script, eos=None):
    """``script``: ``[(iteration, prompt index, SamplingParams kwargs)]``. One
    loop iteration offers what is due and steps the engine if it has work.
    ``eos``: ``{script position: eos ids}``. Returns per script position
    ``(tokens, finish reason, the on_token stream)`` and the outputs count."""
    streams, reqs, outs = {}, {}, []
    it = 0
    while len(reqs) < len(script) or engine.has_work():
        for k, (due, p, kw) in enumerate(script):
            if due == it:
                params = SamplingParams(eos_token_ids=(eos or {}).get(k, ()), **kw)
                reqs[k] = engine.add_request(
                    PROMPTS[p], params,
                    on_token=lambda r, t, k=k: streams.setdefault(k, []).append(t),
                )
        if engine.has_work():
            outs += engine.step()
        # nothing dispatched may be forgotten: in flight counts as work
        assert engine.has_work() or engine._inflight is None
        it += 1
        assert it < 400
    outs += engine.run()
    assert not engine.has_work() and engine._inflight is None
    by_id = {o.request_id: o for o in outs}
    assert len(by_id) == len(outs) == len(script)  # every output, once
    return {
        k: (by_id[r.request_id].token_ids, by_id[r.request_id].finish_reason, streams.get(k, []))
        for k, r in reqs.items()
    }


SAMPLED = dict(do_sample=True, top_k=6, temperature=1.3)


def _script(sampled: bool, slots: int):
    kw = SAMPLED if sampled else {}
    if slots == 1:
        # one slot: the streams follow each other; the last may end on an EOS
        return [(0, 1, dict(max_new_tokens=7, **kw)), (0, 4, dict(max_new_tokens=1, **kw)),
                (3, 2, dict(max_new_tokens=9, **kw)), (4, 0, dict(max_new_tokens=12, **kw))], 3
    # a full batch with staggered arrivals, mixed lengths, a late joiner into a
    # freed slot, and (position 2, the highest row while it runs) an EOS stream
    # (it ends within its first tokens, while both other rows run on)
    return [(0, 1, dict(max_new_tokens=15, **kw)), (0, 4, dict(max_new_tokens=11, **kw)),
            (1, 0, dict(max_new_tokens=16, **kw)), (12, 3, dict(max_new_tokens=5, **kw)),
            (13, 2, dict(max_new_tokens=9, **kw))], 2


@pytest.mark.parametrize("slots", [1, 3], ids=["one-slot", "full-batch"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "seeded"])
def test_chained_and_collect_first_serve_the_same_tokens(tiny_hf_llama, sampled, slots):
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(
        hf_model, hf_cfg,
        on_device_sampling_config=OnDeviceSamplingConfig(do_sample=sampled),
    )
    script, eos_at = _script(sampled, slots)

    def engine():
        return InferenceEngine(app, SchedulerConfig(num_slots=slots), seed=11)

    # the EOS stream's id: a token from the middle of its own stream
    free = serve(engine(), script)[eos_at][0]
    j = next(j for j in range(2, 7) if free[j] not in free[:j])
    eos = {eos_at: (free[j],)}

    chained = engine()
    got = serve(chained, script, eos)
    assert chained_steps(chained), "the chain never engaged"
    overrun = app.telemetry.registry.get("nxdi_decode_overrun_tokens_total").value()
    assert overrun >= 1  # the EOS could not be foreseen: one token was dispatched past it

    collect_first(app)
    sync = engine()
    want = serve(sync, script, eos)
    assert not chained_steps(sync)
    assert app.telemetry.registry.get("nxdi_decode_overrun_tokens_total").value() == overrun

    assert got == want  # tokens, finish reasons, each stream's on_token order and count
    assert got[eos_at][1] == "eos" and got[eos_at][0] == free[: j + 1]
    assert {k: v[1] for k, v in got.items() if k != eos_at} == {
        k: "length" for k in got if k != eos_at
    }
    for toks, _, stream in got.values():
        assert stream == toks


def test_decode_next_ids_takes_device_rows_and_host_rows():
    prev = np.array([[11], [22], [33], [44]], dtype=np.int32)
    rows = np.array([2, -1, 0, -1], dtype=np.int32)
    host = np.array([[0], [7], [0], [9]], dtype=np.int32)
    assert np.asarray(decode_next_ids(prev, rows, host)).tolist() == [[33], [7], [11], [9]]


def test_an_eos_in_the_middle_of_a_full_batch_frees_the_slot_for_a_waiter(tiny_hf_llama):
    """Greedy, the EOS row in the MIDDLE of the batch and a request waiting for
    its slot: the overrun token is dropped, the counter rises by one, the
    blocks return to the pool, and the waiter (seated in the freed slot, over
    the freed blocks) produces what it produces alone."""
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(hf_model, hf_cfg, pa_num_blocks=24)
    alone = {}
    for p in range(4):
        e = InferenceEngine(app, SchedulerConfig(num_slots=3))
        e.add_request(PROMPTS[p], SamplingParams(max_new_tokens=12))
        alone[p] = e.run()[0].token_ids
    mid = alone[1]
    j = next(j for j in range(2, len(mid)) if mid[j] not in mid[:j])

    engine = InferenceEngine(app, SchedulerConfig(num_slots=3))
    counter = app.telemetry.registry.get("nxdi_decode_overrun_tokens_total")
    before = counter.value()
    seen = []
    reqs = [
        engine.add_request(PROMPTS[0], SamplingParams(max_new_tokens=12)),
        engine.add_request(PROMPTS[1], SamplingParams(max_new_tokens=12, eos_token_ids=(mid[j],)),
                           on_token=lambda r, t: seen.append(t)),
        engine.add_request(PROMPTS[2], SamplingParams(max_new_tokens=12)),
        engine.add_request(PROMPTS[3], SamplingParams(max_new_tokens=12)),  # waits for a slot
    ]
    outs = []
    while reqs[1].state != "FINISHED":
        outs += engine.step()
    # the step that learnt of the EOS had already dispatched the row again
    assert engine._inflight is not None
    assert any(r is reqs[1] for _, r, _ in engine._inflight.rows)
    free_then = engine.block_manager.num_free_blocks()
    outs += engine.step()  # collects the overrun token: dropped
    assert counter.value() == before + 1
    assert seen == mid[: j + 1] and reqs[1].generated == mid[: j + 1]
    rec = [r for r in engine.flight.snapshot_records() if r.overrun_tokens]
    assert len(rec) == 1 and rec[0].overrun_tokens == 1 and rec[0].chained
    assert rec[0].decode["tokens_emitted"] == len(rec[0].decode["rows"]) - 1
    # the waiter took the freed slot (and blocks from the freed pool)
    assert reqs[3].slot == 1 and engine.block_manager.num_free_blocks() < free_then
    outs += engine.run()
    got = {o.request_id: o for o in outs}
    assert got[reqs[1].request_id].finish_reason == "eos"
    for p in (0, 2, 3):
        assert got[reqs[p].request_id].token_ids == alone[p]
    assert engine.block_manager.num_free_blocks() == 24  # every block came back
    assert counter.value() == before + 1


# -- drain paths: what needs the host's tokens collects first -----------------

def _streams(outs):
    return {o.request_id: o.token_ids for o in outs}


def _emitted_once(engine_reqs, seen):
    """Every token reached ``on_token`` once, in order: none twice, none lost."""
    for r in engine_reqs:
        assert seen[r.request_id] == r.generated, (r, seen[r.request_id])


def test_pool_exhaustion_with_a_dispatch_in_flight_collects_then_preempts(tiny_hf_llama):
    """A pool too small for both sequences: the step that cannot grow its rows
    finds a decode in flight, collects it, THEN preempts; the victim's replay
    starts from every token the device had made, and both streams are those of
    the uninterrupted runs."""
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(hf_model, hf_cfg, pa_num_blocks=8, tkg_batch_size=2)
    alone = {}
    for p in (0, 1):
        e = InferenceEngine(app, SchedulerConfig(num_slots=2, watermark_blocks=1))
        e.add_request(PROMPTS[p], SamplingParams(max_new_tokens=12))
        alone[p] = e.run()[0].token_ids

    engine = InferenceEngine(app, SchedulerConfig(num_slots=2, watermark_blocks=1))
    seen = {}
    cb = lambda r, t: seen.setdefault(r.request_id, []).append(t)  # noqa: E731
    reqs = [engine.add_request(PROMPTS[p], SamplingParams(max_new_tokens=12), on_token=cb)
            for p in (0, 1)]
    outs = []
    while engine.has_work():
        in_flight = engine._inflight is not None
        before = sum(r.preemptions for r in reqs)
        outs += engine.step()
        if sum(r.preemptions for r in reqs) > before and in_flight:
            # the flight was collected ahead of the preemption: nothing of it is left,
            # and the victim holds every token that had been dispatched for it
            victim = next(r for r in reqs if r.state != "RUNNING")
            assert victim.pending == 0 and seen[victim.request_id] == victim.generated
    assert app.telemetry.serve_preemptions_total.value() >= 1, "sized to exhaust the pool"
    assert any(r.preemptions for r in reqs)
    assert chained_steps(engine)
    got = _streams(outs)
    assert [got[r.request_id] for r in reqs] == [alone[0], alone[1]]
    _emitted_once(reqs, seen)
    assert app.telemetry.registry.get("nxdi_decode_overrun_tokens_total").value() == 0


def test_a_forced_preemption_between_steps_drops_the_token_in_flight(tiny_hf_llama):
    """``preempt_youngest()`` from outside a step, a decode in flight: the
    victim's token in flight is dropped at its collect (its replay makes it
    again), never emitted twice; the survivor's is emitted."""
    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(hf_model, hf_cfg, tkg_batch_size=2)
    alone = {}
    for p in (0, 1):
        e = InferenceEngine(app, SchedulerConfig(num_slots=2))
        e.add_request(PROMPTS[p], SamplingParams(max_new_tokens=10))
        alone[p] = e.run()[0].token_ids
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
    seen = {}
    cb = lambda r, t: seen.setdefault(r.request_id, []).append(t)  # noqa: E731
    reqs = [engine.add_request(PROMPTS[p], SamplingParams(max_new_tokens=10), on_token=cb)
            for p in (0, 1)]
    outs = engine.step() + engine.step() + engine.step()
    assert engine._inflight is not None and reqs[1].pending == 1
    held = list(reqs[1].generated)
    victim = engine.preempt_youngest()
    assert victim is reqs[1] and victim.pending == 0
    outs += engine.step()
    assert victim.generated[: len(held)] == held
    outs += engine.run()
    got = _streams(outs)
    assert [got[r.request_id] for r in reqs] == [alone[0], alone[1]]
    _emitted_once(reqs, seen)
    assert app.telemetry.registry.get("nxdi_decode_overrun_tokens_total").value() == 1


@pytest.mark.parametrize("site", ["engine.step", "dispatch.forward"])
def test_a_step_fault_with_a_dispatch_in_flight_loses_no_token(tiny_hf_llama, site):
    """A transient fault at the top of a step, or inside the next dispatch,
    while the previous decode is in flight: recovery collects the flight (its
    tokens are emitted once), requeues the rows, and the replay is exact."""
    from nxdi_tpu.runtime import faults

    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(hf_model, hf_cfg, tkg_batch_size=2)
    alone = {}
    for p in (0, 1):
        e = InferenceEngine(app, SchedulerConfig(num_slots=2))
        e.add_request(PROMPTS[p], SamplingParams(max_new_tokens=10))
        alone[p] = e.run()[0].token_ids
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
    seen = {}
    cb = lambda r, t: seen.setdefault(r.request_id, []).append(t)  # noqa: E731
    reqs = [engine.add_request(PROMPTS[p], SamplingParams(max_new_tokens=10), on_token=cb)
            for p in (0, 1)]
    outs = engine.step() + engine.step() + engine.step() + engine.step()
    assert engine._inflight is not None and all(r.pending == 1 for r in reqs)
    held = {r.request_id: len(r.generated) for r in reqs}
    plan = faults.FaultPlan([faults.FaultRule(site, "nth", n=1, kind="transient")])
    with faults.armed(plan):
        outs += engine.step()
    assert plan.fired.get(site) == 1
    assert engine._inflight is None and engine._recovery_requeues.total() == 2
    for r in reqs:  # the flight's token arrived before the requeue
        assert r.pending == 0 and len(r.generated) == held[r.request_id] + 1
    outs += engine.run()
    got = _streams(outs)
    assert [got[r.request_id] for r in reqs] == [alone[0], alone[1]]
    _emitted_once(reqs, seen)


def test_a_fault_that_takes_the_flight_drops_it_and_the_replay_makes_it_again(tiny_hf_llama):
    """The collect itself fails (the device's fault surfaces at the fetch):
    the flight's tokens are gone, nothing of them was emitted, the rows requeue
    and their replay makes the same tokens."""
    from nxdi_tpu.runtime import faults

    hf_model, hf_cfg = tiny_hf_llama
    app = build_app(hf_model, hf_cfg, tkg_batch_size=2)
    e = InferenceEngine(app, SchedulerConfig(num_slots=2))
    e.add_request(PROMPTS[0], SamplingParams(max_new_tokens=10))
    alone = e.run()[0].token_ids
    engine = InferenceEngine(app, SchedulerConfig(num_slots=2))
    seen = []
    req = engine.add_request(PROMPTS[0], SamplingParams(max_new_tokens=10),
                             on_token=lambda r, t: seen.append(t))
    engine.step(), engine.step(), engine.step()
    assert engine._inflight is not None
    real, calls = engine._tokens_of, []

    def broken(outputs):
        calls.append(1)
        raise faults.TransientDispatchError("injected at the fetch")

    engine._tokens_of = broken
    held = list(req.generated)
    assert engine.step() == []
    engine._tokens_of = real
    assert calls and engine._inflight is None
    assert req.generated == held and req.pending == 0 and req.preemptions == 1
    (out,) = engine.run()
    assert out.token_ids == alone and seen == alone


def test_handoff_export_of_a_parked_request_and_import_beside_a_flight(tiny_hf_llama):
    """Disaggregated pair: a prefill-role engine never decodes, so a parked
    request is exported with nothing in flight and its payload holds exactly
    the one token it emitted; the decode-role engine seats the import between
    two steps, a decode in flight, and the row joins with a host id."""
    hf_model, hf_cfg = tiny_hf_llama
    unified = build_app(hf_model, hf_cfg, tkg_batch_size=2)
    alone = {}
    for p in (0, 1):
        e = InferenceEngine(unified, SchedulerConfig(num_slots=2))
        e.add_request(PROMPTS[p], SamplingParams(max_new_tokens=9))
        alone[p] = e.run()[0].token_ids

    pf = InferenceEngine(build_app(hf_model, hf_cfg, tkg_batch_size=2, role="prefill"),
                         SchedulerConfig(num_slots=2))
    dc = InferenceEngine(build_app(hf_model, hf_cfg, tkg_batch_size=2, role="decode"),
                         SchedulerConfig(num_slots=2))
    first = {}
    reqs = [pf.add_request(PROMPTS[p], SamplingParams(max_new_tokens=9),
                           on_token=lambda r, t, p=p: first.setdefault(p, []).append(t))
            for p in (0, 1)]
    while pf.has_work():
        assert pf.step() == []
    assert pf._inflight is None and not pf.has_work()  # parked: waits on the ack, not a step
    payloads = {}
    for p, r in enumerate(reqs):
        payloads[p] = pf.export_handoff(r.request_id)
        assert payloads[p].first_tokens == first[p] == alone[p][:1]

    seen = {}
    cb = lambda r, t: seen.setdefault(r.request_id, []).append(t)  # noqa: E731
    a = dc.admit_handoff(payloads[0], on_token=cb)
    outs = dc.step() + dc.step()
    assert dc._inflight is not None and a.pending == 1
    b = dc.admit_handoff(payloads[1], on_token=cb)  # joins mid-flight
    outs += dc.run()
    for r in reqs:
        pf.ack_handoff(r.request_id)
    got = _streams(outs)
    assert got[a.request_id] == alone[0] and got[b.request_id] == alone[1]
    assert chained_steps(dc)
    # the decode side streams what follows the handed-off token, each once
    assert seen[a.request_id] == alone[0][1:] and seen[b.request_id] == alone[1][1:]
