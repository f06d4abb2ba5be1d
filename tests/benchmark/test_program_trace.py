"""The readers of what the PROGRAM writes about its own step (PR 26): the
``StepRecord.phases`` readers on hand-made records, and ``program_trace`` on a
hand-made trace and on a small trace recorded on the v5e with the program's
spans, kernel names and module names in it.

Nothing here is a measurement."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, program_trace, records, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e-3


def _step(t0, t1, rows=0, prefills=0, phases=None):
    ns = SimpleNamespace(
        t_start=t0, t_end=t1, prefills=[{}] * prefills, preempted=[],
        decode={"rows": [{"slot": i, "request_id": i} for i in range(rows)]} if rows else None,
        kv_blocks_free=None,
    )
    if phases is not None:
        ns.phases = {k: v * MS for k, v in phases.items()}
    return ns


def _run(steps, trace=None, notes=None):
    return records.RunRecords(
        seconds=10.0, t_open=0.0, t_close=10.0, t_host_end=10.0, setup_s=5.0, served=[],
        population=[], tokens_in_window=0, steps=steps, counters={}, slots=4, pool_blocks=100,
        block_size=128, tp=1, config={}, traffic={}, device_kind="x", trace=trace,
        notes=notes or {},
    )


def _read(name, run):
    return cells.load_plugin("per_layer", name)(run)


def test_phase_readers_on_hand_made_step_records():
    full = dict(schedule=0.2, kv=0.1, pack=0.5, pad=0.3, enqueue=0.9, fetch=30.0, emit=1.0)
    steps = [
        _step(0.000, 0.035, rows=4, phases=full),  # decode only: host 5.0, wrapper 1.2
        _step(0.035, 0.072, rows=4, phases={**full, "schedule": 0.4, "enqueue": 1.3, "fetch": 31.0}),  # 6.0, 1.6
        _step(0.072, 0.140, rows=4, prefills=1,  # a prefill rode along: only the scheduler's reader takes it
              phases={**full, "schedule": 0.9, "fetch": 60.0}),
        _step(0.140, 0.176, rows=3, phases={**full, "schedule": 0.3, "pad": 0.5, "fetch": 29.0}),  # 7.0, 1.4
        _step(0.176, 0.180, phases={"schedule": 5.0}),  # ran nothing: no reader takes it
    ]
    run = _run(steps)
    assert _read("sched.schedule_ms", run) == pytest.approx(0.35)  # median of .2 .4 .9 .3
    assert _read("dispatch.host_ms", run) == pytest.approx(1.4)  # median of 1.2 1.6 1.4
    assert _read("engine.host_ms", run) == pytest.approx(6.0)  # median of 5 6 7
    # engine.host_ms + the median fetch is the step's wall, which the accepted reader times
    assert _read("engine.step_wall_ms", run) == pytest.approx(36.0)
    # the trace readers find no traced run
    assert _read("kernel.paged_decode_ms", run) is None
    assert _read("device.idle_unattributed_pct", run) is None


@pytest.mark.parametrize("name", ["sched.schedule_ms", "dispatch.host_ms", "engine.host_ms"])
def test_phase_readers_leave_the_metric_out_for_a_program_without_phases(name):
    """The parent commit's StepRecord has no ``phases``: nothing to read,
    nothing raised, the line leaves the metric out."""
    assert _read(name, _run([_step(0.0, 0.035, rows=4), _step(0.035, 0.070, rows=4)])) is None
    assert _read(name, _run([_step(0.0, 0.035, rows=4, phases={})])) is None
    assert _read(name, _run([])) is None


def _planes():
    """Two token-generation executions and one prefill on one chip. us -> ns."""
    us = 1000
    tkg = "jit_token_generation_model_4096__abc123_1(77)"
    cte = "jit_context_encoding_model_256__abc123_0(78)"
    kern = "%paged_attention_decode.3 = bf16[8,2,8,128]{3,2,1,0} custom-call(%bt, %q)"
    ops = [
        # execution 1 of the decode program: 100..400
        ["%fusion.1 = bf16[8]{0} fusion(%p)", 100 * us, 50 * us],
        ["%while = (s32[]) while(%t)", 150 * us, 250 * us],
        [kern, 160 * us, 40 * us], [kern, 260 * us, 60 * us],
        # the prefill: 500..700, whose kernel has a name of its own; a decode
        # kernel event outside any decode execution is not counted
        ["%flash_attention_prefill.2 = bf16[1]{0} custom-call(%q)", 520 * us, 100 * us],
        ["%fusion.9 = bf16[8]{0} fusion(%p)", 500 * us, 200 * us],
        # execution 2: 1000..1300
        ["%fusion.1 = bf16[8]{0} fusion(%p)", 1000 * us, 300 * us],
        [kern, 1010 * us, 30 * us],
        ["%paged_attention_decode_v2.1 = bf16[1]{0} custom-call(%q)", 1100 * us, 10 * us],
    ]
    host = [
        ["bench.engine_step", 0, 450 * us], ["bench.engine_step", 450 * us, 450 * us],
        ["bench.engine_step", 900 * us, 500 * us],
        ["nxdi.step", 5 * us, 440 * us],
        ["nxdi.step.pack", 10 * us, 60 * us],
        ["nxdi.step.fetch", 80 * us, 330 * us],
        ["nxdi.step.emit", 410 * us, 30 * us],
        ["nxdi.step.fetch", 600 * us, 250 * us],
        ["nxdi.step.emit", 850 * us, 200 * us],
        ["PjitFunction(x)", 20 * us, 5 * us],
    ]
    return {
        "/device:TPU:0": {
            "XLA Modules": [[tkg, 100 * us, 300 * us], [cte, 500 * us, 200 * us],
                            [tkg, 1000 * us, 300 * us]],
            "XLA Ops": ops,
        },
        "/host:CPU": {"python3": host, "worker": [["nxdi.step.not_here", 0, 0]]},
    }


def test_kernel_time_per_execution_by_the_kernels_own_name():
    planes = _planes()
    assert program_trace.instruction_name("%copy.65 = bf16[3]{0} copy(%p)") == "copy.65"
    got = program_trace.kernel_s_per_execution(planes, "paged_attention_decode")
    assert got == pytest.approx((40 + 60 + 30) * 1e-6 / 2)
    assert program_trace.kernel_s_per_execution(
        planes, "flash_attention_prefill", "jit_context_encoding_model") == pytest.approx(100e-6)
    assert program_trace.kernel_s_per_execution(planes, "kv_commit_rows") is None  # not in the trace
    # a program whose modules are named per process (the parent commit): nothing to read
    old = json.loads(json.dumps(planes).replace("jit_token_generation_model_4096__", "jit_"))
    assert program_trace.kernel_s_per_execution(old, "paged_attention_decode") is None
    assert program_trace.kernel_s_per_execution({"/host:CPU": {}}, "paged_attention_decode") is None


def test_idle_time_under_no_phase_of_the_step():
    planes = _planes()
    gaps = program_trace.idle_gaps_ns(planes)
    us = 1000
    assert gaps == [(0, 100 * us), (400 * us, 500 * us), (700 * us, 1000 * us), (1300 * us, 1400 * us)]
    # the same gaps as the accepted reducer labels, over the same window
    summary = trace_reduce.reduce_trace(planes)
    assert sum(s for _, s in summary.idle_gaps) == pytest.approx(sum(b - a for a, b in gaps) / 1e9)
    assert 1.0 - summary.idle_pct_worst / 100.0 == pytest.approx(800 / 1400)
    # what the phases overlap of each gap, in us: 0..100 pack 60 + fetch 20; 400..500 fetch 10 +
    # emit 30; 700..1000 fetch 150 + emit 150; 1300..1400 nothing. 180 of 600 us under no phase
    assert program_trace.idle_by_phase_s(planes) == pytest.approx(
        {"pack": 60e-6, "fetch": 180e-6, "emit": 180e-6, "no phase": 180e-6})
    assert program_trace.idle_unattributed_pct(planes) == pytest.approx(30.0)
    # a program that writes no phase spans (the parent commit): nothing to read
    bare = dict(planes)
    bare["/host:CPU"] = {"python3": [e for e in planes["/host:CPU"]["python3"]
                                     if not e[0].startswith("nxdi.")]}
    assert program_trace.idle_unattributed_pct(bare) is None and program_trace.idle_by_phase_s(bare) == {}
    assert program_trace.idle_unattributed_pct({"/host:CPU": planes["/host:CPU"]}) is None


def test_trace_readers_load_the_runs_own_trace_once(tmp_path, monkeypatch):
    """``of(run)`` finds the newest ``.xplane.pb`` under ``.bench_trace``,
    loads it once per run, and is None in a run that was not traced."""
    old = tmp_path / "cell-a" / "plugins" / "profile" / "2026_01_01"
    new = tmp_path / "cell-b" / "plugins" / "profile" / "2026_01_02"
    for i, d in enumerate((old, new)):
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (1000 + i, 1000 + i))
    assert program_trace.newest_xplane(str(tmp_path)) == str(new / "host.xplane.pb")
    assert program_trace.newest_xplane(str(tmp_path / "nothing")) is None
    loads = []
    monkeypatch.setattr(program_trace, "TRACE_ROOT", str(tmp_path))
    monkeypatch.setattr(program_trace, "newest_xplane", lambda root=None: str(new / "host.xplane.pb"))
    monkeypatch.setattr(program_trace, "load_xplane", lambda path: loads.append(path) or _planes())
    traced = _run([], trace=object())
    assert _read("kernel.paged_decode_ms", traced) == pytest.approx(65e-3)
    assert _read("device.idle_unattributed_pct", traced) == pytest.approx(30.0)
    assert len(loads) == 1
    assert program_trace.of(_run([])) is None and len(loads) == 1


def test_program_trace_on_the_recorded_v5e_trace_with_the_programs_names():
    """A cut of a real trace of qwen25-3b.decode-saturated on the v5e, recorded
    WITH this program's spans and names (PR 26): three engine steps, one of
    which also ran a CTE[256] prefill. The expected numbers were computed by
    plain loops over the file, outside ``program_trace`` (``expect.how``)."""
    with open(os.path.join(HERE, "recorded_trace_v5e_phases.json")) as f:
        rec = json.load(f)
    planes, want = rec["planes"], rec["expect"]
    modules = [trace_reduce.module_base(e[0]) for e in planes["/device:TPU:0"]["XLA Modules"]]
    assert sum(m.startswith("jit_token_generation_model_4096__") for m in modules) == want["tkg_executions"] == 3
    assert sum(m.startswith("jit_context_encoding_model_256__") for m in modules) == 1
    # the two kernels are told apart by their own names; nothing is called `name`
    instr = {program_trace.instruction_name(e[0]) for e in planes["/device:TPU:0"]["XLA Ops"]}
    assert {"paged_attention_decode.3", "flash_attention_prefill.3"} <= instr
    assert not any(i == "name" or i.startswith(("name.", "tpu_custom_call")) for i in instr)
    got = program_trace.kernel_s_per_execution(planes, "paged_attention_decode")
    assert got * 1e3 == pytest.approx(want["kernel_paged_decode_ms"], rel=1e-9)
    assert 8.0 < got * 1e3 < 9.0  # 36 layers x ~0.23 ms
    assert program_trace.kernel_s_per_execution(
        planes, "flash_attention_prefill", "jit_context_encoding_model"
    ) * 1e3 == pytest.approx(want["prefill_kernel_ms_per_cte"], rel=1e-9)
    assert program_trace.kernel_s_per_execution(planes, "flash_attention_prefill") is None  # not in the decode program
    gaps = program_trace.idle_gaps_ns(planes)
    assert len(gaps) == want["idle_gaps"] and sum(b - a for a, b in gaps) == want["idle_ns"]
    assert program_trace.idle_unattributed_pct(planes) == pytest.approx(want["idle_unattributed_pct"], rel=1e-9)
    by_phase = program_trace.idle_by_phase_s(planes)
    for phase, ns in want["idle_by_phase_ns"].items():
        assert by_phase[phase] == pytest.approx(ns / 1e9, rel=1e-9)
    assert max(by_phase, key=by_phase.get) == "fetch"  # the tail after the program ends
    # every engine step of the cut is one nxdi.step span holding its phases
    host = [e for line in planes["/host:CPU"].values() for e in line]
    steps = sorted((e for e in host if e[0] == "nxdi.step"), key=lambda e: e[1])
    assert len(steps) == 3
    for name, start, dur in program_trace.phase_spans(planes):
        assert any(s <= start and start + dur <= s + d for _, s, d in steps), name
    # the accepted reducer reads the same file, its idle gaps now labelled by the program's phases
    summary = trace_reduce.reduce_trace(planes)
    assert summary.device_ops[0][0] == "paged_attention_decode.3 custom-call"
    assert any("nxdi.step." in label for label, _ in summary.idle_gaps)
