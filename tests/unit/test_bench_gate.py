"""scripts/bench_gate.py: the bench-trajectory regression gate (tier-2).
Stdlib-only module loaded from its file path (scripts/ is not a package)."""

import importlib.util
import json
import os

_SPEC = importlib.util.spec_from_file_location(
    "bench_gate",
    os.path.join(os.path.dirname(__file__), "..", "..", "scripts", "bench_gate.py"),
)
bench_gate = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(bench_gate)


BASE = {
    "value": 3700.0,
    "tkg_step_p50_ms": 8.64,
    "cte_p50_ms": 683.0,
    "cte_mfu_pct": 60.0,
    "mfu_pct": 4.6,
    "hbm_roofline_pct": 90.0,
    "bs1_tok_ms": None,  # cached side file absent in this round
}


def _write(tmp_path, name, d):
    p = tmp_path / name
    p.write_text(json.dumps(d))
    return str(p)


def test_within_tolerance_passes(tmp_path):
    fresh = dict(BASE, value=3650.0, tkg_step_p50_ms=8.8)  # ~1-2% noise
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fresh),
        "--baseline", _write(tmp_path, "base.json", BASE),
        "-q",
    ])
    assert rc == 0


def test_regression_fails_and_reports(tmp_path, capsys):
    fresh = dict(BASE, tkg_step_p50_ms=11.0)  # +27% step latency
    out = tmp_path / "rows.json"
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fresh),
        "--baseline", _write(tmp_path, "base.json", BASE),
        "--json", str(out),
    ])
    assert rc == 1
    err = capsys.readouterr().err
    assert "tkg_step_p50_ms" in err and "REGRESSION" in err
    rows = json.loads(out.read_text())["rows"]
    (bad,) = [r for r in rows if r["regression"]]
    assert bad["metric"] == "tkg_step_p50_ms"


def test_improvement_passes_both_directions(tmp_path):
    # higher-is-better metric up AND lower-is-better metric down = all good
    fresh = dict(BASE, value=5000.0, tkg_step_p50_ms=6.0, mfu_pct=7.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fresh),
        "--baseline", _write(tmp_path, "base.json", BASE),
        "-q",
    ])
    assert rc == 0


def test_mfu_field_regression_gates(tmp_path):
    # the new CostSheet-sourced fields are first-class gated metrics
    fresh = dict(BASE, hbm_roofline_pct=70.0)  # -22%
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fresh),
        "--baseline", _write(tmp_path, "base.json", BASE),
        "-q",
    ])
    assert rc == 1


def test_missing_and_null_metrics_skip(tmp_path, capsys):
    # bs1_tok_ms is None in the baseline; spec_tok_s missing on both sides —
    # neither may crash or count as a regression
    fresh = dict(BASE)
    fresh["bs1_tok_ms"] = 12.0
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fresh),
        "--baseline", _write(tmp_path, "base.json", BASE),
    ])
    assert rc == 0
    assert "bs1_tok_ms" in capsys.readouterr().err  # listed as skipped


def test_tolerance_scale(tmp_path):
    fresh = dict(BASE, value=3400.0)  # -8.1%: fails at 1x, passes at 2x
    base = _write(tmp_path, "base.json", BASE)
    f = _write(tmp_path, "fresh.json", fresh)
    assert bench_gate.main([f, "--baseline", base, "-q"]) == 1
    assert bench_gate.main(
        [f, "--baseline", base, "-q", "--tolerance-scale", "2.0"]
    ) == 0


def test_wrapped_trajectory_baseline_unwraps(tmp_path):
    # the repo's BENCH_r*.json files store the bench record under "parsed"
    # (next to the driver's n/cmd/rc wrapper) — the gate must unwrap it
    wrapped = {"n": 5, "cmd": "python bench.py", "rc": 0, "parsed": dict(BASE)}
    fresh = dict(BASE, tkg_step_p50_ms=11.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fresh),
        "--baseline", _write(tmp_path, "base.json", wrapped),
        "-q",
    ])
    assert rc == 1  # the wrapped baseline's metrics were actually compared


def _write_trajectory(tmp_path, *rounds):
    """Driver-wrapped trajectory records (the bench line under "parsed"),
    written where the gate globs for them."""
    for n in rounds:
        wrapped = {"n": n, "cmd": "python bench.py", "rc": 0, "parsed": dict(BASE)}
        _write(tmp_path, f"BENCH_r{n:02d}.json", wrapped)


def test_gate_against_trajectory_file(tmp_path):
    # a trajectory file vs itself: every comparable metric is identical -> pass
    _write_trajectory(tmp_path, 5)
    r05 = str(tmp_path / "BENCH_r05.json")
    assert bench_gate.main([r05, "--baseline", r05, "-q"]) == 0
    rec = bench_gate.bench_record(json.load(open(r05)))
    rows, _ = bench_gate.compare(rec, rec, bench_gate.TOLERANCES)
    assert rows, "trajectory file yielded no comparable metrics"


def test_default_baseline_picks_latest_round(tmp_path):
    # a root that carries a BENCH_r*.json trajectory: the gate must pick the
    # newest round; a root without one has no default
    assert bench_gate.default_baseline(str(tmp_path)) is None
    _write_trajectory(tmp_path, 4, 5)
    picked = bench_gate.default_baseline(str(tmp_path))
    assert picked is not None and os.path.basename(picked) == "BENCH_r05.json"


def test_usage_errors(tmp_path):
    assert bench_gate.main([str(tmp_path / "missing.json"),
                            "--baseline", str(tmp_path / "nope.json")]) == 2


def test_fleet_metrics_gate_and_skip_when_absent(tmp_path):
    """bench.py --serving --replicas N emits fleet_* headline fields:
    one-sided gating, skipped against pre-fleet baselines, and the generic
    'value' row suppressed for fleet-mode fresh records (their req/s
    headline must not gate against a decode-mode tok/s baseline)."""
    fleet = {
        "value": 1.6,
        "fleet_replicas": 2,
        "fleet_goodput_req_s": 1.6,
        "fleet_tok_s": 410.0,
        "fleet_straggler_gap_pct": 12.0,
        "fleet_slo_attainment_pct": 96.0,
        "fleet_goodput_slo_tok_s": 400.0,
    }
    # pre-fleet baseline (decode-mode BASE): every fleet_* field skips and
    # the suppressed "value" row cannot fail the run
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fleet),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0
    rows, skipped = bench_gate.compare(BASE, fleet, bench_gate.TOLERANCES)
    assert "fleet_tok_s" in skipped and "fleet_straggler_gap_pct" in skipped

    # same-shape baseline: a goodput drop beyond tolerance fails...
    worse = dict(fleet, fleet_tok_s=330.0, fleet_goodput_req_s=1.3)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", worse),
        "--baseline", _write(tmp_path, "base.json", fleet),
        "-q",
    ])
    assert rc == 1
    # ... a straggler-gap blowout fails (lower is better, one-sided) ...
    straggly = dict(fleet, fleet_straggler_gap_pct=40.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", straggly),
        "--baseline", _write(tmp_path, "base.json", fleet),
        "-q",
    ])
    assert rc == 1
    # ... and a gap IMPROVEMENT plus in-tolerance noise passes (one-sided)
    better = dict(fleet, fleet_straggler_gap_pct=2.0, fleet_tok_s=402.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", better),
        "--baseline", _write(tmp_path, "base.json", fleet),
        "-q",
    ])
    assert rc == 0


def test_routed_metrics_gate_and_failover_absolute(tmp_path):
    """bench.py --serving --replicas N --routed emits routed_* headline
    fields: one-sided gating, skipped against pre-router baselines, the
    generic 'value' row suppressed for routed-mode fresh records, and the
    failover/error counts gated ABSOLUTELY (< 1 — nothing dies in a
    healthy routed bench, so any failover is a bug, baseline or not)."""
    routed = {
        "value": 1.5,
        "routed_replicas": 2,
        "routed_goodput_req_s": 1.5,
        "routed_tok_s": 390.0,
        "routed_ttft_p50_ms": 260.0,
        "routed_ttft_p95_ms": 1100.0,
        "routed_failovers": 0.0,
        "routed_errors": 0,
        "routed_drains": 1.0,
    }
    # pre-router baseline (decode-mode BASE): every routed_* comparison
    # skips, the suppressed "value" row cannot fail, and the ABSOLUTE
    # failover gate still passes at 0
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", routed),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0
    rows, skipped = bench_gate.compare(BASE, routed, bench_gate.TOLERANCES)
    assert "routed_tok_s" in skipped and "routed_ttft_p95_ms" in skipped

    # a single failover fails ABSOLUTELY even against a pre-router baseline
    failover = dict(routed, routed_failovers=1.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", failover),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 1
    # an error-finished request too
    errored = dict(routed, routed_errors=2)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", errored),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 1

    # same-shape baseline: a routed goodput drop beyond tolerance fails...
    worse = dict(routed, routed_tok_s=320.0, routed_goodput_req_s=1.2)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", worse),
        "--baseline", _write(tmp_path, "base.json", routed),
        "-q",
    ])
    assert rc == 1
    # ... in-tolerance noise and a TTFT improvement pass (one-sided)
    better = dict(routed, routed_ttft_p50_ms=200.0, routed_tok_s=385.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", better),
        "--baseline", _write(tmp_path, "base.json", routed),
        "-q",
    ])
    assert rc == 0


def test_chaos_retention_absolute_gate(tmp_path):
    """bench.py --serving --chaos emits chaos_* fields: the goodput
    retention is ABSOLUTE-gated (>= 70, higher-is-better — a ratio of two
    same-run passes needs no baseline), chaos_recovery_p95_ms gates
    one-sided against same-shape baselines and skips against pre-chaos
    ones, and the generic 'value' row (the retention pct) is suppressed
    so it never gates against a decode-mode tok/s baseline."""
    chaos = {
        "value": 88.0,
        "chaos_goodput_retention_pct": 88.0,
        "chaos_recovery_p95_ms": 45.0,
        "chaos_stream_mismatches": 0,
        "chaos_errors": 0,
        "chaos_requeues": 3,
        "chaos_injected": 9,
    }
    # pre-chaos baseline (decode-mode BASE): chaos_* comparisons skip, the
    # suppressed "value" row cannot fail, and the ABSOLUTE floor passes
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", chaos),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0
    rows, skipped = bench_gate.compare(BASE, chaos, bench_gate.TOLERANCES)
    assert "chaos_recovery_p95_ms" in skipped

    # retention under the 70% floor fails ABSOLUTELY, baseline or not
    leaky = dict(chaos, value=55.0, chaos_goodput_retention_pct=55.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", leaky),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 1

    # same-shape baseline: recovery-latency blowup beyond the (wide)
    # tolerance fails; an improvement passes one-sided
    slow = dict(chaos, chaos_recovery_p95_ms=90.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", slow),
        "--baseline", _write(tmp_path, "base.json", chaos),
        "-q",
    ])
    assert rc == 1
    fast = dict(chaos, chaos_recovery_p95_ms=20.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fast),
        "--baseline", _write(tmp_path, "base.json", chaos),
        "-q",
    ])
    assert rc == 0


def test_qos_metrics_absolute_gate(tmp_path):
    """bench.py --serving --multi-tenant emits the QoS control-plane
    headline pair, both ABSOLUTE-gated (no baseline needed): interactive
    attainment >= 80 and Jain fairness >= 0.8. The generic 'value' row
    (the attainment pct) is suppressed so it never gates against a
    decode-mode tok/s baseline."""
    qos = {
        "value": 96.0,
        "qos_slo_attainment_pct_interactive": 96.0,
        "qos_slo_attainment_pct_batch": 100.0,
        "qos_fairness_jain": 0.97,
        "qos_goodput_tok_s": 800.0,
    }
    # pre-QoS baseline (decode-mode BASE): qos_* comparisons skip, the
    # suppressed "value" row cannot fail, both ABSOLUTE floors pass
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", qos),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0

    # interactive attainment under the 80 floor fails ABSOLUTELY
    breached = dict(qos, value=60.0,
                    qos_slo_attainment_pct_interactive=60.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", breached),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 1

    # a starved tenant (Jain under 0.8) fails even with attainment held
    unfair = dict(qos, qos_fairness_jain=0.55)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", unfair),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 1

    # a missing side (autoscale-mode record, say) skips both floors
    rows, _ = bench_gate.check_absolute(
        {"autoscale_cycle_ok": True}, bench_gate.ABSOLUTE_LIMITS
    )
    assert not any(r["metric"].startswith("qos_") for r in rows)


def test_mixed_metrics_gate_and_skip_when_absent(tmp_path):
    """bench.py --serving --mixed-dispatch emits mixed_* headline fields:
    one-sided gating (goodput higher, padding waste lower), skipped against
    pre-mixed baselines, and the generic 'value' row suppressed for
    mixed-mode fresh records (their tok/s headline must not gate against a
    decode-mode tok/s/chip baseline)."""
    mixed = {
        "value": 430.0,
        "mixed_goodput_tok_s": 430.0,
        "mixed_goodput_req_s": 1.7,
        "mixed_padding_waste_pct": 22.0,
        "unmixed_padding_waste_pct": 41.0,
    }
    # pre-mixed baseline (decode-mode BASE): every mixed_* field skips and
    # the suppressed "value" row cannot fail the run
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", mixed),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0
    rows, skipped = bench_gate.compare(BASE, mixed, bench_gate.TOLERANCES)
    assert "mixed_goodput_tok_s" in skipped
    assert "mixed_padding_waste_pct" in skipped

    # same-shape baseline: a goodput drop beyond tolerance fails...
    worse = dict(mixed, mixed_goodput_tok_s=350.0, value=350.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", worse),
        "--baseline", _write(tmp_path, "base.json", mixed),
        "-q",
    ])
    assert rc == 1
    # ... a padding-waste blowout fails (lower is better: the packer or the
    # token-bucket ladder fragmented) ...
    wasteful = dict(mixed, mixed_padding_waste_pct=35.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", wasteful),
        "--baseline", _write(tmp_path, "base.json", mixed),
        "-q",
    ])
    assert rc == 1
    # ... and a waste IMPROVEMENT plus in-tolerance noise passes (one-sided)
    better = dict(mixed, mixed_padding_waste_pct=15.0, mixed_goodput_tok_s=425.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", better),
        "--baseline", _write(tmp_path, "base.json", mixed),
        "-q",
    ])
    assert rc == 0


def test_prefix_metrics_gate_and_skip_when_absent(tmp_path):
    """bench.py --serving --prefix-cache emits the prefix-cache headline
    pair: one-sided gating (hit rate AND goodput higher-is-better), skipped
    against pre-prefix baselines, and the generic 'value' row suppressed
    for prefix-mode fresh records (their tok/s headline must not gate
    against a decode-mode tok/s/chip baseline)."""
    prefix = {
        "value": 410.0,
        "prefix_goodput_tok_s": 410.0,
        "prefix_hit_rate_pct": 96.8,
        "noprefix_goodput_tok_s": 360.0,
    }
    # pre-prefix baseline (decode-mode BASE): every prefix_* field skips
    # and the suppressed "value" row cannot fail the run
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", prefix),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0
    rows, skipped = bench_gate.compare(BASE, prefix, bench_gate.TOLERANCES)
    assert "prefix_goodput_tok_s" in skipped
    assert "prefix_hit_rate_pct" in skipped

    # same-shape baseline: a hit-rate collapse fails (the radix match or
    # the retire-insert path broke — near-deterministic on this workload)
    cold = dict(prefix, prefix_hit_rate_pct=60.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", cold),
        "--baseline", _write(tmp_path, "base.json", prefix),
        "-q",
    ])
    assert rc == 1
    # ... a goodput drop beyond tolerance fails ...
    slow = dict(prefix, prefix_goodput_tok_s=350.0, value=350.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", slow),
        "--baseline", _write(tmp_path, "base.json", prefix),
        "-q",
    ])
    assert rc == 1
    # ... and in-tolerance noise passes (one-sided: improvements free)
    fine = dict(prefix, prefix_hit_rate_pct=97.0, prefix_goodput_tok_s=405.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fine),
        "--baseline", _write(tmp_path, "base.json", prefix),
        "-q",
    ])
    assert rc == 0


def test_device_loop_metrics_gate_and_skip_when_absent(tmp_path):
    """bench.py --device-loop emits the resident-loop A/B pair:
    device_loop_ms_per_tok gates lower-is-better, tokens-per-dispatch
    higher-is-better (a drop means launches exit early or the cap ladder
    regressed), and both skip against pre-loop baselines."""
    loop = dict(
        BASE,
        device_loop_ms_per_tok=9.1,
        device_loop_tokens_per_dispatch=128.0,
        tkg_multistep_ms_per_token=10.4,
    )
    # pre-loop baseline: both device_loop_* fields skip
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", loop),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0
    rows, skipped = bench_gate.compare(BASE, loop, bench_gate.TOLERANCES)
    assert "device_loop_ms_per_tok" in skipped
    assert "device_loop_tokens_per_dispatch" in skipped

    # same-shape baseline: a per-token regression beyond tolerance fails...
    slower = dict(loop, device_loop_ms_per_tok=10.5)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", slower),
        "--baseline", _write(tmp_path, "base.json", loop),
        "-q",
    ])
    assert rc == 1
    # ... launches retiring fewer tokens per dispatch fails ...
    shallow = dict(loop, device_loop_tokens_per_dispatch=96.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", shallow),
        "--baseline", _write(tmp_path, "base.json", loop),
        "-q",
    ])
    assert rc == 1
    # ... and improvements on both pass (one-sided)
    better = dict(
        loop,
        device_loop_ms_per_tok=8.4,
        device_loop_tokens_per_dispatch=256.0,
    )
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", better),
        "--baseline", _write(tmp_path, "base.json", loop),
        "-q",
    ])
    assert rc == 0


def test_sentinel_overhead_absolute_gate(tmp_path, capsys):
    """sentinel_overhead_pct (bench.py --serving numerics-sentinel smoke)
    gates against the ABSOLUTE < 3% limit on the fresh record alone: it
    never needs a baseline (pre-sentinel trajectories cannot make it
    vacuous) and is skipped, not failed, when the smoke did not run."""
    ok = dict(BASE, sentinel_overhead_pct=1.4)
    base = _write(tmp_path, "base.json", BASE)  # pre-sentinel baseline
    rc = bench_gate.main([_write(tmp_path, "ok.json", ok), "--baseline", base])
    assert rc == 0
    assert "sentinel_overhead_pct" in capsys.readouterr().err

    # over the limit fails even though the baseline has no such field...
    hot = dict(BASE, sentinel_overhead_pct=4.5)
    rc = bench_gate.main(
        [_write(tmp_path, "hot.json", hot), "--baseline", base, "-q"]
    )
    assert rc == 1
    # ... exactly at the limit fails too (strictly under 3%) ...
    at = dict(BASE, sentinel_overhead_pct=3.0)
    rc = bench_gate.main(
        [_write(tmp_path, "at.json", at), "--baseline", base, "-q"]
    )
    assert rc == 1
    # ... a negative measurement (noise: sentinel side faster) passes ...
    neg = dict(BASE, sentinel_overhead_pct=-0.4)
    rc = bench_gate.main(
        [_write(tmp_path, "neg.json", neg), "--baseline", base, "-q"]
    )
    assert rc == 0
    # ... and absence (smoke skipped / null) is a skip, not a failure
    rows, skipped = bench_gate.check_absolute(
        dict(BASE, sentinel_overhead_pct=None), bench_gate.ABSOLUTE_LIMITS
    )
    assert rows == [] and "sentinel_overhead_pct" in skipped
    rc = bench_gate.main(
        [_write(tmp_path, "plain.json", BASE), "--baseline", base, "-q"]
    )
    assert rc == 0


def test_trace_overhead_and_attribution_absolute_gates(tmp_path, capsys):
    """The distributed-tracing pair from bench.py --serving --routed gates
    on the fresh record alone: trace_overhead_pct strictly under 3%
    (lower-is-better ceiling, like the sentinel), and
    trace_ttft_attribution_pct strictly over 90% (higher-is-better floor —
    the critical path must actually explain the client TTFT it claims
    to). Absence of either field skips, never fails."""
    base = _write(tmp_path, "base.json", BASE)  # pre-tracing baseline
    ok = dict(BASE, trace_overhead_pct=0.8, trace_ttft_attribution_pct=97.2)
    rc = bench_gate.main([_write(tmp_path, "ok.json", ok), "--baseline", base])
    assert rc == 0
    err = capsys.readouterr().err
    assert "trace_overhead_pct" in err
    assert "trace_ttft_attribution_pct" in err

    # tracing costing 3% or more fails on the fresh record alone ...
    hot = dict(ok, trace_overhead_pct=3.0)
    rc = bench_gate.main(
        [_write(tmp_path, "hot.json", hot), "--baseline", base, "-q"]
    )
    assert rc == 1
    # ... attribution at or under the 90% floor fails ...
    thin = dict(ok, trace_ttft_attribution_pct=90.0)
    rc = bench_gate.main(
        [_write(tmp_path, "thin.json", thin), "--baseline", base, "-q"]
    )
    assert rc == 1
    # ... negative overhead (noise: traced side faster) passes ...
    neg = dict(ok, trace_overhead_pct=-0.5)
    rc = bench_gate.main(
        [_write(tmp_path, "neg.json", neg), "--baseline", base, "-q"]
    )
    assert rc == 0
    # ... and null / absent fields are skips, not failures
    rows, skipped = bench_gate.check_absolute(
        dict(BASE, trace_overhead_pct=None), bench_gate.ABSOLUTE_LIMITS
    )
    assert rows == []
    assert "trace_overhead_pct" in skipped
    assert "trace_ttft_attribution_pct" in skipped
    rc = bench_gate.main(
        [_write(tmp_path, "plain.json", BASE), "--baseline", base, "-q"]
    )
    assert rc == 0


def test_serving_metrics_gate_and_skip_when_absent(tmp_path):
    """The bench.py --serving goodput line gates one-sided; a baseline from
    BEFORE the serving engine (no serving_* fields) skips them instead of
    failing."""
    serving = {
        "value": 1.8,
        "serving_goodput_req_s": 1.8,
        "serving_tok_s": 450.0,
        "serving_ttft_p50_ms": 220.0,
        "serving_ttft_p95_ms": 900.0,
        "serving_tpot_p50_ms": 9.0,
        "serving_tpot_p95_ms": 14.0,
    }
    # old baseline without serving metrics: everything serving_* skips
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", serving),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0
    rows, skipped = bench_gate.compare(BASE, serving, bench_gate.TOLERANCES)
    assert "serving_tok_s" in skipped and "serving_ttft_p95_ms" in skipped

    # the "value" suppression keys on the FRESH side only: a decode-mode
    # record keeps its headline gate even against a trajectory baseline
    # that folded serving_* fields in (side-file folding)
    folded_base = dict(BASE, serving_goodput_req_s=1.8)
    regressed = dict(BASE, value=BASE["value"] * 0.5)
    rc = bench_gate.main([
        _write(tmp_path, "fresh_decode.json", regressed),
        "--baseline", _write(tmp_path, "base_folded.json", folded_base),
        "-q",
    ])
    assert rc == 1

    # same-shape baseline: a goodput drop beyond tolerance fails...
    worse = dict(serving, serving_tok_s=380.0, serving_goodput_req_s=1.5)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", worse),
        "--baseline", _write(tmp_path, "base.json", serving),
        "-q",
    ])
    assert rc == 1
    # ... while a TTFT improvement (lower) plus in-tolerance noise passes
    better = dict(serving, serving_ttft_p50_ms=150.0, serving_tpot_p95_ms=14.5)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", better),
        "--baseline", _write(tmp_path, "base.json", serving),
        "-q",
    ])
    assert rc == 0


def test_disagg_metrics_gate_and_skip_when_absent(tmp_path):
    """bench.py --serving --disaggregated emits the disaggregation headline
    triple: one-sided gating (goodput higher; TPOT p95 and handoff p50
    lower), skipped against pre-disagg baselines, and the generic 'value'
    row suppressed for disagg-mode fresh records (their tok/s headline must
    not gate against a decode-mode tok/s/chip baseline)."""
    disagg = {
        "value": 420.0,
        "disagg_goodput_tok_s": 420.0,
        "disagg_tpot_p95_ms": 12.0,
        "disagg_handoff_p50_ms": 35.0,
        "unified_goodput_tok_s": 400.0,
        "unified_tpot_p95_ms": 18.0,
    }
    # pre-disagg baseline (decode-mode BASE): every disagg_* field skips
    # and the suppressed "value" row cannot fail the run
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", disagg),
        "--baseline", _write(tmp_path, "base_old.json", BASE),
        "-q",
    ])
    assert rc == 0
    rows, skipped = bench_gate.compare(BASE, disagg, bench_gate.TOLERANCES)
    assert "disagg_goodput_tok_s" in skipped
    assert "disagg_tpot_p95_ms" in skipped
    assert "disagg_handoff_p50_ms" in skipped

    # same-shape baseline: a goodput drop beyond tolerance fails...
    slow = dict(disagg, disagg_goodput_tok_s=350.0, value=350.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", slow),
        "--baseline", _write(tmp_path, "base.json", disagg),
        "-q",
    ])
    assert rc == 1
    # ... a TPOT p95 blowout fails (lower is better: decode steps stalling
    # again means the role split or the dispatch path regressed) ...
    stalled = dict(disagg, disagg_tpot_p95_ms=16.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", stalled),
        "--baseline", _write(tmp_path, "base.json", disagg),
        "-q",
    ])
    assert rc == 1
    # ... a handoff-latency blowout fails (the fetch->place->ack span is
    # the migration cost every request pays once) ...
    sticky = dict(disagg, disagg_handoff_p50_ms=60.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", sticky),
        "--baseline", _write(tmp_path, "base.json", disagg),
        "-q",
    ])
    assert rc == 1
    # ... and improvements plus in-tolerance noise pass (one-sided)
    fine = dict(disagg, disagg_tpot_p95_ms=11.0, disagg_goodput_tok_s=415.0,
                disagg_handoff_p50_ms=30.0)
    rc = bench_gate.main([
        _write(tmp_path, "fresh.json", fine),
        "--baseline", _write(tmp_path, "base.json", disagg),
        "-q",
    ])
    assert rc == 0
