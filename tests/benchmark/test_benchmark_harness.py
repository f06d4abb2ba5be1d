"""The benchmark's own code on the CPU: generators, arithmetic, the trace
reducer on a trace recorded on the v5e, the references against the app at a
toy size, the shape of the last line, and the refusal to run off a TPU.

Nothing here is a measurement: every time, rate or share printed by these
tests comes from the CPU backend and is compared with nothing.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, costs, records, traffic_gen, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
from toys import quiet_run, served_by, toy_config, toy_steady_cell  # noqa: E402

BIG_SEED = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits


# -- traffic ------------------------------------------------------------------

def _traffic_files():
    d = os.path.join(cells.BENCH_DIR, "traffic")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("traffic", _traffic_files())
def test_generator_is_deterministic_and_a_seed_reorders_one_multiset(traffic):
    params = cells.read_json(cells.traffic_path(traffic))
    gen = cells.load_plugin("generator", params["generator"])
    a = gen(params, 7, 20.0, 1000, 8)
    b = gen(params, 7, 20.0, 1000, 8)
    c = gen(params, BIG_SEED, 20.0, 1000, 8)
    assert [(o.due_s, o.prompt, o.max_new) for o in a] == [(o.due_s, o.prompt, o.max_new) for o in b]
    assert [o.prompt for o in a] != [o.prompt for o in c]
    shape = lambda offers: [(len(o.prompt), o.max_new) for o in offers]  # noqa: E731
    assert shape(a) != shape(c), "a seed draws the order the work arrives in"
    for column in (0, 1):  # ... and never the work: the same lengths, as multisets
        assert sorted(x[column] for x in shape(a)) == sorted(x[column] for x in shape(c))
    gaps = lambda offers: np.diff([0.0] + [o.due_s for o in offers])  # noqa: E731
    assert np.allclose(np.sort(gaps(a)), np.sort(gaps(c)))
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))
    assert a[-1].due_s == pytest.approx(c[-1].due_s)
    lo, hi = params["prompt_len"]["lo"], params["prompt_len"]["hi"]
    assert all(lo <= len(o.prompt) <= hi and all(0 <= t < 1000 for t in o.prompt) for o in a)
    assert max(len(o.prompt) for o in a) <= traffic_gen.max_prompt_len(params)
    assert all(o.max_new <= params["output_len"]["hi"] for o in a)
    assert "assumed" in params or "lengths_source" in params  # lengths cite a source or say they do not


def test_length_grids():
    u = traffic_gen.length_grid({"dist": "uniform", "lo": 64, "hi": 256}, 1000)
    assert u.min() == 64 and u.max() == 256 and abs(u.mean() - 160) < 1
    ln = traffic_gen.length_grid(
        {"dist": "lognormal", "median": 256, "sigma": 1.0, "lo": 32, "hi": 2048}, 1001
    )
    assert ln.min() >= 32 and ln.max() == 2048 and int(np.median(ln)) == 256
    gaps = traffic_gen.exponential_gaps(5.0, 2000)
    assert gaps.sum() == pytest.approx(2000 / 5.0, rel=0.01)


# -- arithmetic ---------------------------------------------------------------

def test_percentile_matches_numpy():
    xs = [0.3, 0.1, 0.9, 0.5, 0.7, 0.2]
    for p in (0, 50, 95, 100):
        assert records.percentile(xs, p) == pytest.approx(np.percentile(xs, p))
    assert records.percentile([], 95) is None and records.median([4.0]) == 4.0


def _served(i, want, tokens, reason="length", error=None, ttft=0.1, finished=1.0, times=()):
    out = SimpleNamespace(
        token_ids=tokens, finish_reason=reason, error=error,
        metrics={"ttft_s": ttft, "preemptions": 0},
    )
    return records.Served(i, 0.0, 0.0, 10, want, SimpleNamespace(span=None), out, finished,
                          token_times=list(times))


def _run(population, **kw):
    base = dict(
        seconds=10.0, t_open=100.0, t_close=110.0, t_host_end=110.0, setup_s=5.0,
        served=population, population=population, tokens_in_window=0, steps=[], counters={},
        slots=4, pool_blocks=100, block_size=128, tp=1, config={}, traffic={}, device_kind="x",
    )
    base.update(kw)
    return records.RunRecords(**base)


def test_failed_requests_and_latency_readers():
    good = [_served(i, 3, [1, 2, 3], ttft=0.1 * (i + 1)) for i in range(20)]
    bad = [
        _served(20, 3, [1, 2]),  # ended early
        _served(21, 3, [1, 2, 3], reason="error", error="boom"),
        _served(22, 3, [1, 2, 999999]),  # id outside the vocabulary
        records.Served(23, 0.0, 0.0, 10, 3, SimpleNamespace(span=None)),  # missed the drain cap
    ]
    population = good + bad
    for s in population:
        s.fault = records.fault_of(s, vocab=1000)
    assert [s.fault is None for s in population] == [True] * 20 + [False] * 4
    run = _run(population, tokens_in_window=1234)
    read = lambda kind, name: cells.load_plugin(kind, name)(run)  # noqa: E731
    assert run.metric_of_ok("ttft_s") == pytest.approx([0.1 * (i + 1) for i in range(20)])  # the clean ones only
    assert read("end_to_end", "out_tok_s") == pytest.approx(123.4)
    assert cells.load_plugin("end_to_end", "out_tok_s")(_run([])) is None


def test_token_gaps_are_those_inside_the_window():
    """The gap tail is over every stream's gaps with both tokens in the window:
    a stream seated in the ramp gives its later gaps, one still running at the
    close gives its earlier ones, the drain gives none; a failed one none."""
    ramp = _served(0, 5, [1] * 5, times=[98.0, 99.5, 100.5, 100.6, 100.8])  # gaps .1, .2
    running = records.Served(1, 0, 0, 10, 9, SimpleNamespace(span=None),
                             token_times=[108.0, 108.5, 109.5, 110.5, 111.0])  # .5, 1.0
    failed = _served(2, 3, [1, 2], times=[101.0, 104.0])
    population = [ramp, failed]
    for s in population:
        s.fault = records.fault_of(s, vocab=1000)
    run = _run(population, served=[ramp, running, failed])
    assert sorted(run.token_gaps()) == pytest.approx([0.1, 0.2, 0.5, 1.0])
    assert cells.load_plugin("end_to_end", "tpot_p99_ms")(run) == pytest.approx(
        1e3 * np.percentile([0.1, 0.2, 0.5, 1.0], 99))
    traced = _run(population, served=[ramp, running, failed], t_host_end=109.0)
    assert sorted(traced.token_gaps()) == pytest.approx([0.1, 0.2, 0.5])  # the profiler's part left out
    assert cells.load_plugin("end_to_end", "tpot_p99_ms")(_run([])) is None


def _step(t0, t1, rows=0, prefills=0, free=None, preempted=0):
    return SimpleNamespace(
        t_start=t0, t_end=t1, prefills=[{}] * prefills, preempted=[{}] * preempted,
        decode={"rows": [{"slot": i, "request_id": i} for i in range(rows)]} if rows else None,
        kv_blocks_free=free,
    )


def test_step_record_and_counter_readers():
    steps = [
        _step(0.0, 0.010, rows=4, free=60), _step(0.010, 0.022, rows=4, free=40),
        _step(0.022, 0.060, rows=3, prefills=1, free=50, preempted=1), _step(0.060, 0.074, rows=2, free=55),
    ]
    run = _run([], steps=steps, counters={
        "nxdi_real_tokens_total|context_encoding_model": 300.0,
        "nxdi_padded_tokens_total|context_encoding_model": 512.0,
    })
    read = lambda name: cells.load_plugin("per_layer", name)(run)  # noqa: E731
    assert read("engine.step_wall_ms") == pytest.approx(12.0)  # median of 10, 12, 14: prefill step left out
    assert read("engine.decode_rows_mean") == pytest.approx(13 / 4)
    assert read("sched.prefill_step_pct") == pytest.approx(25.0)  # one of the four decode steps
    assert read("kv.preemptions") == 1.0
    assert read("kv.pool_used_peak_pct") == pytest.approx(60.0)
    assert read("cte.pad_waste_pct") == pytest.approx(100 * (1 - 300 / 512))
    assert read("tkg.device_ms") is None and read("device.idle_pct") is None  # no trace: nothing to read


def test_step_floor_from_shapes():
    cfg = cells.read_json("benchmark/configs/qwen25-3b.json")
    tkg_step = cells.load_plugin("cost_model", cfg["benchmark"]["cost_model"])
    work = tkg_step(cfg, 64, 30000)
    assert work["bytes"] == pytest.approx(2 * 3.0858e9 + 30000 * 36864, rel=1e-3)
    assert work["flops"] == pytest.approx(2 * 3.0858e9 * 64, rel=1e-3)
    peaks = costs.peaks_of("TPU v5 lite")
    least = costs.least_s(work, 1, peaks)
    assert least["bound"] == "bytes" and 0.008 < least["least_s"] < 0.010
    assert costs.least_s(work, 4, peaks)["least_s"] == pytest.approx(least["least_s"] / 4)
    assert costs.least_s({"bytes": 1.0, "flops": 1e9}, 1, peaks)["bound"] == "flops"
    with pytest.raises(KeyError):
        costs.peaks_of("cpu")
    mistral = cells.read_json("benchmark/configs/mistral-7b-tp4.json")
    assert cells.load_plugin("cost_model", mistral["benchmark"]["cost_model"])(mistral, 1, 0)[
        "bytes"] == pytest.approx(2 * 7.1138e9, rel=1e-3)  # 7.25 B less the input embedding


# -- the trace reducer --------------------------------------------------------

def test_union_and_host_labels():
    total, merged = trace_reduce.union_ns([(0, 10), (5, 20), (30, 40), (35, 36)])
    assert total == 30 and merged == [(0, 20), (30, 40)]
    assert trace_reduce.module_base("jit_abc_1(123456)") == "jit_abc_1"
    hlo = "%copy.65 = bf16[36,122880,2,128]{3,2,1,0:T(2,128)(2,1)} copy(bf16[36,122880,2,128]{3,2,1,0} %p)"
    assert trace_reduce.op_label(hlo) == "copy.65 copy"
    assert trace_reduce.op_label("%while = (s32[]{:T(128)}, bf16[64,1,2048]{2,0,1:T(8,128)(2,1)S(1)}) while(%t)") == "while while"
    assert trace_reduce.op_label("fusion.1") == "fusion.1"
    # a while spanning two body ops, then a lone op: self time leaves the body out
    events = [["w", 0, 100], ["a", 10, 30], ["b", 50, 40], ["c", 120, 5]]
    assert trace_reduce.self_ns(events) == [30, 30, 40, 5]


def test_reducer_on_a_synthetic_two_chip_trace():
    ops = lambda shift: [  # noqa: E731
        ["fusion.1", 1000 + shift, 4000], ["all-reduce.2", 5000 + shift, 1000],
        ["fusion.1", 156000 + shift, 4000], ["all-reduce.2", 160000 + shift, 1000],
    ]
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [["jit_tok_0(1)", 1000, 5000], ["jit_tok_0(1)", 156000, 5000]],
            "XLA Ops": ops(0),
        },
        "/device:TPU:1": {"XLA Ops": ops(0)[:2], "XLA Modules": []},
        "/host:CPU": {"main": [
            ["bench.engine_step", 0, 150000], ["PjitFunction(x)", 500, 400],
            ["copy_to_host", 20000, 120000], ["bench.engine_step", 150000, 50000],
        ]},
    }
    s = trace_reduce.reduce_trace(planes, {"jit_tok_0": "token_generation_model[4096]"})
    assert s.devices == 2 and s.window_s == pytest.approx(200e-6)
    assert s.busy_s == pytest.approx((10e-6 + 5e-6) / 2)
    assert s.idle_pct_worst == pytest.approx(100 * (1 - 5 / 200))
    assert s.tkg_device_s() == pytest.approx(5e-6)
    assert s.collective_s_per_tkg == pytest.approx(1e-6)
    assert s.device_ops[0] == ["fusion.1", pytest.approx(8e-6)]
    assert s.idle_gaps[0][0] == "bench.engine_step>copy_to_host"
    assert trace_reduce.reduce_trace({"/host:CPU": planes["/host:CPU"]}) is None


def test_reducer_on_the_recorded_v5e_trace():
    """A cut of a real trace of qwen25-3b.decode-saturated on the v5e (PR 24):
    a few engine steps, device ops merged per fusion name as recorded."""
    with open(os.path.join(HERE, "recorded_trace_v5e.json")) as f:
        rec = json.load(f)
    s = trace_reduce.reduce_trace(rec["planes"], rec["module_labels"])
    want = rec["expect"]
    assert s.devices == want["devices"]
    assert len(s.module_s[want["tkg_label"]]) == want["tkg_executions"]
    assert s.tkg_device_s() == pytest.approx(want["tkg_device_s"], rel=1e-6)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert 0.0 <= s.idle_pct_worst <= 100.0
    assert s.busy_s <= s.window_s
    assert s.idle_gaps and all(label.startswith("bench.") for label, _ in s.idle_gaps[:1])
    assert s.device_ops[0][0] == want["top_op"]  # the paged decode kernel, as the trace names it
    assert s.collective_s_per_tkg is None  # one chip: no collective to read


# -- the references against the app, and the last line ------------------------

def _toy(model_type, tied, tp=1):
    cfg = toy_config(model_type, tie_word_embeddings=tied,
                     a_later_family_key={"experts": 8})  # no list in the harness knows this one
    cfg["benchmark"].update(chips=tp, tp=tp)
    return cfg


@pytest.mark.parametrize("model_type,tied,tp", [("qwen2", True, 1), ("mistral", False, 2)])
def test_reference_agrees_with_the_app_at_a_toy_size(model_type, tied, tp):
    """Both families of today's configurations: q/k/v biases with a tied head,
    and no bias with an untied head over two (virtual) chips. A reference with
    a dropped bias or a dropped layer must fail the same check."""
    from benchmark import correctness, serving_app
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    cfg = _toy(model_type, tied, tp)
    app = serving_app.build_app(cfg, [256], seed=BIG_SEED)
    # every published key reaches the program's config class, the benchmark's own do not
    assert app.config.a_later_family_key == {"experts": 8}
    assert not any(hasattr(app.config, k) for k in serving_app.BENCHMARK_KEYS)
    app.load()
    assert serving_app.strategy_faults(app, cfg["benchmark"]["attention_strategies"]) == []
    assert serving_app.strategy_faults(app, {"token_generation_model": "tkg_two_part_xla"})
    engine = InferenceEngine(app, SchedulerConfig(num_slots=4))
    reference = cells.load_plugin("reference", "dense_decoder")
    samples = correctness.sample_served(served_by(engine, 3), 3, tokens=120)
    got = correctness.program_probe(app, correctness.probe_prompt(3, 256), 256)
    assert app.kv_cache is not None and getattr(app, "_logit_probe", None) is None
    said = []
    assert correctness.check(app.params, cfg, reference, 3, got, samples, said.append)["ok"], said

    def wrong(params, config, ids):
        if model_type == "qwen2":
            params = dict(params, layers=dict(params["layers"]))
            attn = dict(params["layers"]["attn"])
            attn["v_proj"] = {"w": attn["v_proj"]["w"]}
            params["layers"]["attn"] = attn
            return reference(params, config, ids)
        return reference(params, dict(config, num_hidden_layers=1), ids)

    assert not correctness.check(app.params, cfg, wrong, 3, got, samples, said.append)["ok"]


def test_run_cell_prints_the_contract_line(monkeypatch):
    """The whole run at a toy size through the harness's own functions. Off a
    TPU ``main`` refuses; ``run_cell`` is what it calls after the device check."""
    import jax

    from benchmark import run as bench_run

    quiet_run(monkeypatch)
    manifest = cells.load_manifest()
    cell = toy_steady_cell(_toy("qwen2", True))
    said = []
    line = bench_run.run_cell(cell, BIG_SEED, 3.0, False, jax.devices()[:1], said.append)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert list(line["compared"])[:3] == ["probe_mse", "probe_diff", "served_gap"]  # each number beside its limit
    assert all(sorted(c) == ["limit", "value"] and c["value"] <= c["limit"] for c in line["compared"].values())
    assert sorted(line["device"]) == ["count", "kind", "memory_peak_bytes", "platform"]
    assert line["device"]["platform"] == "cpu"  # names its device: never read as a chip's
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 9
    assert set(line["metrics"]) == {m["name"] for m in manifest["end_to_end"]}
    for name, m in line["metrics"].items():
        assert sorted(m) == ["unit", "value"] and m["value"] > 0, name
    json.dumps(line)
    assert any("compilations in window 0" in s for s in said), said


def test_sweep_runs_a_window_per_rate_and_refuses_off_a_tpu(monkeypatch):
    import jax

    from benchmark import run as bench_run
    from benchmark import sweep

    assert sweep.main(["--config", "qwen25-3b", "--traffic", "chat-steady", "--rates", "1"]) \
        == bench_run.EXIT_NO_DEVICE
    quiet_run(monkeypatch)
    cell = toy_steady_cell(_toy("qwen2", True))
    cell.traffic["output_len"] = dict(cell.traffic["output_len"], median=6, hi=8)
    traffic = cell.traffic
    said = []
    prep = bench_run.prepare(cell, 5, jax.devices()[:1], said.append)
    rows = sweep.sweep(prep, cell, [4.0, 2.0], 5, 2.0, said.append)
    assert [r["rate_per_s"] for r in rows] == [2.0, 4.0] and [r["offered"] for r in rows] == [4, 8]
    manifest_e2e = {m["name"] for m in cells.load_manifest()["end_to_end"]} - {"setup_s"}
    for r in rows:
        assert manifest_e2e <= set(r) and r["failed"] == 0 and r["tpot_p99_ms"] > 0
        assert isinstance(r["sustained"], bool) and r["slots_busy_q4"] <= 4
    # a rate that offers more tokens a second than any window completed is not sustained
    assert all(not r["sustained"] for r in rows if r["offered_tok_s"] > max(x["completed_tok_s"] for x in rows))
    assert cell.traffic is traffic  # the cell is handed back as it came


def test_sets_reads_runs_and_spreads():
    import statistics

    from benchmark import sets

    def stdout(v, ok=True):
        return (f"[bench    1.0s] cell x\n[bench   60.0s]   end_to_end out_tok_s = {v} tokens/s\n"
                f"[bench   60.0s]   per_layer kv.preemptions = 0.0 count\n"
                + json.dumps({"correct": ok, "attempted": 5, "failed": 0, "metrics": {}, "device": {}}))

    values = [100.0, 101.0, 99.0, 103.0, 100.5, 98.0]
    runs = [sets.read_run(stdout(v)) for v in values]
    assert [r["metrics"]["out_tok_s"] for r in runs] == values and runs[0]["line"]["correct"] is True
    q = statistics.quantiles(values, n=4)
    assert sets.spread(values) == pytest.approx((q[2] - q[0]) / statistics.median(values))
    assert sets.read_run("Traceback ...")["line"] is None
    text = "\n".join(sets.table([runs, runs[:3] + [sets.read_run("boom")]]))
    assert "out_tok_s" in text and f"{100 * sets.spread(values):.3f}%" in text and "set 2 / set 1" in text
    assert "kv.preemptions" not in text  # a median of 0 has no spread to give


def test_run_py_refuses_to_run_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    workload = cells.load_manifest()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result line, nothing built
    assert "No CPU fall-back" in proc.stderr
