"""Flight recorder unit suite (nxdi_tpu/telemetry/flight.py): StepRecord
ring semantics, dispatch attribution + the step's phases (and ``host_s`` =
wall - fetch) under an injected clock, postmortem triggers (storm cooldown, retrace trip, manual),
bundle structure, and the Perfetto per-slot track golden."""

import json
from types import SimpleNamespace

import pytest

from nxdi_tpu.telemetry import FlightRecorder, Telemetry


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def make_recorder(num_slots=2, **kw):
    clock = FakeClock()
    tel = Telemetry(clock=clock)
    rec = FlightRecorder(tel, num_slots=num_slots, **kw)
    tel.attach_flight(rec)
    return rec, tel, clock


def req(rid):
    return SimpleNamespace(request_id=rid)


# ---------------------------------------------------------------------------
# ring + step protocol
# ---------------------------------------------------------------------------

def test_step_record_ring_bounded_and_counts_drops():
    rec, tel, clock = make_recorder(max_records=3)
    for i in range(5):
        rec.begin_step()
        clock.advance(0.001)
        rec.end_step(queue_depth=0, slots_busy=0, kv_blocks_free=None)
    assert len(rec.records) == 3
    assert [r.step for r in rec.records] == [2, 3, 4]
    assert rec.records_dropped == 2
    assert rec.summary()["records_dropped"] == 2
    assert tel.registry.get("nxdi_engine_steps_total").total() == 5


def _spend(tel, clock, phase, seconds):
    with tel.phase(phase):
        clock.advance(seconds)


def test_dispatch_attribution_and_host_split():
    rec, tel, clock = make_recorder()
    rec.begin_step()
    rec.record_admission(7, slot=1, resumed=False)
    rec.record_prefill(7, 1, "context_encoding_model", 0, 8)
    # dispatches recorded through the ONE path (Telemetry.record_dispatch)
    # land on the open record with exact program keys
    tel.record_dispatch("context_encoding_model", 32, 1, 0.004)
    tel.record_dispatch("token_generation_model", 64, 1, 0.002)
    tel.record_dispatch("token_generation_model", 64, 1, 0.002)
    # a prefill and a decode in one step: fetch is entered twice and sums
    _spend(tel, clock, "schedule", 0.0005)
    _spend(tel, clock, "fetch", 0.003)
    _spend(tel, clock, "pack", 0.001)
    _spend(tel, clock, "fetch", 0.005)
    clock.advance(0.0005)  # under no phase
    r = rec.end_step(queue_depth=2, slots_busy=1, kv_blocks_free=17)
    assert r.dispatch_s == pytest.approx(0.008)
    assert r.wall_s == pytest.approx(0.010)
    assert r.phases == pytest.approx({"schedule": 0.0005, "fetch": 0.008, "pack": 0.001})
    # host_s is the step less its wait for the device's tokens, whatever
    # record_dispatch timed; what no phase covers stays visible
    assert r.host_s == pytest.approx(r.wall_s - r.phases["fetch"]) == pytest.approx(0.002)
    assert r.other_s == pytest.approx(0.0005)
    assert sum(r.phases.values()) + r.other_s == pytest.approx(r.wall_s)
    d = r.to_dict()
    assert d["phases"] == r.phases and d["other_s"] == pytest.approx(0.0005)
    assert d["host_s"] == pytest.approx(0.002)
    hist = tel.registry.get("nxdi_engine_phase_seconds")
    assert hist.snapshot_series(phase="fetch").sum == pytest.approx(0.008)
    assert hist.snapshot_series(phase="fetch").count == 1  # one observation per step
    assert hist.snapshot_series(phase="emit").count == 0  # pre-seeded, never entered
    assert tel.registry.get("nxdi_engine_host_seconds").snapshot_series().sum == pytest.approx(0.002)
    assert d["programs"] == [
        {"submodel": "context_encoding_model", "bucket": "32", "steps": "1",
         "dispatches": 1, "seconds": pytest.approx(0.004)},
        {"submodel": "token_generation_model", "bucket": "64", "steps": "1",
         "dispatches": 2, "seconds": pytest.approx(0.004)},
    ]
    assert d["admitted"] == [
        {"request_id": 7, "slot": 1, "resumed": False, "cached": 0, "total": 0}
    ]
    assert d["kv_blocks_free"] == 17 and d["queue_depth"] == 2
    # dispatches and phases OUTSIDE a step (static generate traffic)
    # attribute nowhere: the phase only annotates the profiler's trace
    _spend(tel, clock, "pad", 0.001)
    assert rec.current is None and "pad" not in r.phases
    tel.record_dispatch("token_generation_model", 64, 1, 0.002)
    assert rec.current is None
    json.dumps(d)


def test_decode_and_retirement_records():
    rec, tel, clock = make_recorder(num_slots=4)
    rec.begin_step()
    rec.record_decode(
        "token_generation_model_multistep", 4,
        [(0, req(10)), (2, req(11))], batch=4,
    )
    rec.record_retirement(11, 2, "eos")
    clock.advance(0.001)
    r = rec.end_step(0, 1, None)
    assert r.decode == {
        "submodel": "token_generation_model_multistep",
        "steps": 4,
        "rows": [{"slot": 0, "request_id": 10}, {"slot": 2, "request_id": 11}],
        "batch": 4,
        "padding_rows": 2,
        "tokens_emitted": None,
    }
    assert r.retired == [{"request_id": 11, "slot": 2, "reason": "eos"}]


def test_records_overlapping_selects_request_lifetime():
    rec, tel, clock = make_recorder()
    marks = []
    for _ in range(4):
        rec.begin_step()
        t0 = clock.t
        clock.advance(1.0)
        rec.end_step(0, 0, None)
        marks.append(t0)
    # a request alive across steps 1..2 only
    got = rec.records_overlapping(marks[1] + 0.5, marks[2] + 0.5)
    assert [r.step for r in got] == [1, 2]
    # a boundary touch counts as overlap (end == t0)
    got = rec.records_overlapping(marks[3] + 1.0, marks[3] + 9.0)
    assert [r.step for r in got] == [3]


# ---------------------------------------------------------------------------
# triggers
# ---------------------------------------------------------------------------

def test_preemption_storm_fires_once_per_window(tmp_path):
    rec, tel, clock = make_recorder(
        storm_window=4, storm_preemptions=2, postmortem_dir=str(tmp_path)
    )
    def step(preempts):
        rec.begin_step()
        for rid in range(preempts):
            rec.record_preemption(rid, slot=0)
        clock.advance(0.001)
        rec.end_step(0, 0, None)

    step(1)
    assert rec.postmortems == []
    step(1)  # 2 preemptions within the window -> storm
    assert [p["trigger"] for p in rec.postmortems] == ["preemption_storm"]
    step(3)  # still inside the cooldown window: no refire
    assert len(rec.postmortems) == 1
    for _ in range(4):
        step(0)  # cooldown passes
    step(2)
    assert len(rec.postmortems) == 2
    assert tel.registry.get("nxdi_postmortems_total").value(
        trigger="preemption_storm"
    ) == 2
    # bundles landed on disk
    files = sorted(tmp_path.glob("postmortem_preemption_storm_*.json"))
    assert len(files) == 2
    bundle = json.loads(files[0].read_text())
    assert bundle["detail"]["threshold"] == 2


def test_retrace_guard_trip_fires_postmortem():
    guard = SimpleNamespace(violations=[])
    clock = FakeClock()
    tel = Telemetry(clock=clock)
    rec = FlightRecorder(tel, num_slots=1, retrace_guard=guard)
    tel.attach_flight(rec)
    rec.begin_step()
    clock.advance(0.001)
    rec.end_step(0, 0, None)
    assert rec.postmortems == []
    guard.violations.append("tkg[128] lowered AFTER serving started")
    rec.begin_step()
    clock.advance(0.001)
    rec.end_step(0, 0, None)
    assert [p["trigger"] for p in rec.postmortems] == ["retrace_guard"]
    # the trip is edge-triggered: the SAME violation does not refire
    rec.begin_step()
    clock.advance(0.001)
    rec.end_step(0, 0, None)
    assert len(rec.postmortems) == 1
    # the bundle carries the new violation text
    last = rec.postmortem("manual")
    assert last["metrics"]["nxdi_engine_steps_total"]["series"][0]["value"] == 3


def test_manual_postmortem_bundle_structure(tmp_path):
    state = {"waiting": [{"request_id": 5}], "slots": [None, {"request_id": 9}]}
    rec, tel, clock = make_recorder(
        postmortem_dir=str(tmp_path), state_fn=lambda: state
    )
    span = tel.start_request(tokens_in=4)
    rec.begin_step()
    tel.record_dispatch("token_generation_model", 64, 1, 0.001)
    clock.advance(0.002)
    rec.end_step(1, 1, 12)
    span.finish()

    with pytest.raises(ValueError, match="trigger"):
        rec.postmortem("nope")
    bundle = rec.postmortem(
        "manual", detail={"why": "test"}, request_span=span, request_id=123
    )
    assert bundle["trigger"] == "manual"
    assert bundle["request_id"] == 123
    assert bundle["request_span"]["tokens_in"] == 4
    assert len(bundle["step_records"]) == 1
    assert bundle["scheduler"] is state
    # the metrics snapshot is the full one (including the _flight summary)
    assert "nxdi_dispatch_seconds" in bundle["metrics"]
    assert bundle["metrics"]["_flight"]["records"] == 1
    assert bundle["history_dropped"] == 0
    assert bundle["path"] and json.loads(open(bundle["path"]).read())


# ---------------------------------------------------------------------------
# Perfetto per-slot golden
# ---------------------------------------------------------------------------

def test_perfetto_engine_timeline_golden():
    rec, tel, clock = make_recorder(num_slots=2)
    # step 0: admit + prefill request 1 into slot 0 (10 ms, 8 of them
    # waiting for the device's token)
    rec.begin_step()
    rec.record_admission(1, 0, resumed=False)
    rec.record_prefill(1, 0, "context_encoding_model", 0, 8)
    tel.record_dispatch("context_encoding_model", 32, 1, 0.001)
    _spend(tel, clock, "enqueue", 0.001)
    _spend(tel, clock, "fetch", 0.008)
    clock.advance(0.001)
    rec.end_step(0, 1, None)
    # step 1: decode slots 0+1 (4 ms, 3 of them in fetch)
    rec.begin_step()
    rec.record_admission(2, 1, resumed=False)
    rec.record_prefill(2, 1, "context_encoding_model", 0, 5)
    rec.record_decode("token_generation_model", 1, [(0, req(1))], batch=2)
    tel.record_dispatch("token_generation_model", 64, 1, 0.0005)
    _spend(tel, clock, "fetch", 0.003)
    _spend(tel, clock, "emit", 0.001)
    rec.end_step(0, 2, None)
    # step 2: request 2 preempted off slot 1; nothing fetched
    rec.begin_step()
    rec.record_preemption(2, 1)
    rec.record_decode("token_generation_model", 1, [(0, req(1))], batch=2)
    _spend(tel, clock, "kv", 0.002)
    rec.end_step(1, 1, None)

    trace = tel.perfetto_trace()
    json.dumps(trace)
    events = trace["traceEvents"]
    engine = [e for e in events if e.get("pid") == 2]
    # one track per decode slot + the host-overhead track
    tracks = {
        e["tid"]: e["args"]["name"]
        for e in engine if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert tracks == {0: "slot 0", 1: "slot 1", 2: "host overhead"}
    (pname,) = [
        e for e in engine if e["ph"] == "M" and e["name"] == "process_name"
    ]
    assert pname["args"]["name"] == "engine steps (per slot)"

    slices = [e for e in engine if e["ph"] == "X"]
    by_name = {}
    for e in slices:
        by_name.setdefault(e["name"], []).append(e)
    # prefill segments on each slot's track, step-aligned (us, t0-relative)
    assert [(e["tid"], e["ts"], e["dur"]) for e in by_name["prefill"]] == [
        (0, 0.0, 10000.0), (1, 10000.0, 4000.0),
    ]
    assert by_name["prefill"][0]["args"]["request_id"] == 1
    # decode segments carry the rung and the row's request
    assert [(e["tid"], e["ts"]) for e in by_name["decode"]] == [
        (0, 10000.0), (0, 14000.0),
    ]
    assert by_name["decode"][0]["args"]["steps"] == 1
    # the preempted segment lands on the VACATED slot's track
    assert [(e["tid"], e["ts"]) for e in by_name["preempted"]] == [(1, 14000.0)]
    # one host-overhead slice per step, dur = wall - fetch, the phases and
    # the time under none of them in its arguments
    host = [(e["tid"], e["ts"], e["dur"]) for e in by_name["host"]]
    assert host == [
        (2, 0.0, 2000.0), (2, 10000.0, 1000.0), (2, 14000.0, 2000.0),
    ]
    assert by_name["host"][0]["args"]["phases_ms"] == {"enqueue": 1.0, "fetch": 8.0}
    assert by_name["host"][0]["args"]["other_ms"] == 1.0
    assert by_name["host"][2]["args"] == {
        "step": 2, "wall_ms": 2.0, "dispatch_ms": 0.0,
        "phases_ms": {"kv": 2.0}, "other_ms": 0.0,
    }


def test_phase_is_the_shared_null_context_when_telemetry_is_off(monkeypatch):
    """detail="off": no TraceAnnotation is made and nothing is recorded;
    the hot path pays one boolean check."""
    import nxdi_tpu.telemetry as telemetry

    made = []
    monkeypatch.setattr(telemetry, "TraceAnnotation", lambda name: made.append(name))
    monkeypatch.setattr(telemetry, "StepTraceAnnotation", lambda *a, **k: made.append(a))
    off = Telemetry(detail="off", clock=FakeClock())
    assert off.phase("fetch") is off.phase("pack") is off.step_span(3)
    with off.step_span(3), off.phase("fetch"):
        pass
    assert made == []
    assert off.registry.get("nxdi_engine_phase_seconds").series() == {}


def test_phase_annotates_on_the_profilers_clock(monkeypatch):
    """An enabled phase is ``TraceAnnotation("nxdi.step.<phase>")`` and a
    step ``StepTraceAnnotation("nxdi.step", step_num=StepRecord.step)``."""
    import contextlib

    import nxdi_tpu.telemetry as telemetry

    made = []

    def fake(name, **kw):
        made.append((name, kw))
        return contextlib.nullcontext()

    monkeypatch.setattr(telemetry, "TraceAnnotation", fake)
    monkeypatch.setattr(telemetry, "StepTraceAnnotation", fake)
    rec, tel, clock = make_recorder()
    step = rec.begin_step().step
    with tel.step_span(step):
        _spend(tel, clock, "schedule", 0.001)
    rec.end_step(0, 0, None)
    assert made == [("nxdi.step", {"step_num": 0}), ("nxdi.step.schedule", {})]
    with pytest.raises(KeyError):
        tel.phase("not-a-phase")
    assert set(telemetry.PHASES) == {
        "schedule", "kv", "pack", "pad", "enqueue", "fetch", "emit"}


def test_perfetto_without_flight_unchanged():
    clock = FakeClock()
    tel = Telemetry(clock=clock)
    span = tel.start_request(tokens_in=2)
    span.phase("decode")
    clock.advance(1.0)
    span.finish()
    trace = tel.perfetto_trace()
    assert all(e.get("pid") != 2 for e in trace["traceEvents"])


# ---------------------------------------------------------------------------
# spans-dropped accounting (satellite)
# ---------------------------------------------------------------------------

def test_span_ring_overflow_counts_drops():
    clock = FakeClock()
    tel = Telemetry(clock=clock, max_spans=3)
    for _ in range(5):
        tel.start_request().finish()
    assert len(tel.spans.spans) == 3
    assert tel.spans_dropped_total.total() == 2
    # surfaced in the Prometheus export and flagged in bundles
    assert "nxdi_spans_dropped_total 2" in tel.prometheus_text()
    rec = FlightRecorder(tel, num_slots=1)
    tel.attach_flight(rec)
    assert rec.postmortem("manual")["history_dropped"] == 2


def test_spans_dropped_series_visible_before_first_drop():
    tel = Telemetry()
    assert "nxdi_spans_dropped_total 0" in tel.prometheus_text()
