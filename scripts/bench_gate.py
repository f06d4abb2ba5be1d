#!/usr/bin/env python
"""Bench regression gate: a fresh bench.py JSON vs an earlier record, with
per-metric tolerances. The documented tier-2 step after a bench run:

    python bench.py > /tmp/bench_fresh.json
    python scripts/bench_gate.py /tmp/bench_fresh.json --baseline EARLIER.json

Baseline resolution: ``--baseline FILE`` or the newest ``BENCH_r*.json``
(lexicographically last round) in the repo root, where a trajectory is kept
there (the repo carries none today). Metrics missing or null on EITHER side
are skipped with a note — bench modes print different fields, and older
records predate the CostSheet fields.

Exit status: 0 = no metric regressed beyond its tolerance, 1 = regression,
2 = usage error. Improvements and within-tolerance noise both pass (the
gate is one-sided; ratcheting the baseline forward is a human decision).

Stdlib-only on purpose: the gate must run in the bare bench container.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

#: metric -> (direction, relative tolerance). "higher" = bigger is better.
#: Tolerances absorb run-to-run chip noise (p50s over 3-5 chains move ~2-3%
#: on a quiet v5e; MFU fields inherit the p50 noise).
TOLERANCES: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.05),  # headline decode tok/s/chip
    "tkg_step_p50_ms": ("lower", 0.07),
    "tkg_step_p50_ms_int8": ("lower", 0.07),
    "decode_tok_s_int8_weights": ("higher", 0.05),
    "cte_p50_ms": ("lower", 0.10),
    "spec_tok_s": ("higher", 0.10),
    "spec_accept_tokens_per_window": ("higher", 0.10),
    "tkg_multistep_ms_per_token": ("lower", 0.07),
    # device-resident decode loop (bench.py --device-loop; PR: device loop).
    # One-sided and skipped against pre-loop baselines (missing on a side,
    # like every new-mode field). Tokens-per-dispatch is the loop's whole
    # point — a drop means launches are exiting early or the cap ladder
    # regressed — and is near-deterministic, so it gets a tight tolerance.
    "device_loop_ms_per_tok": ("lower", 0.07),
    "device_loop_tokens_per_dispatch": ("higher", 0.02),
    "bs1_tok_ms": ("lower", 0.07),
    "spec_bs1_window_ms": ("lower", 0.07),
    "decode_tok_s_8b_int8": ("higher", 0.05),
    # the CostSheet-joined roofline fields (PR: cost observatory)
    "cte_mfu_pct": ("higher", 0.10),
    "mfu_pct": ("higher", 0.07),
    "hbm_roofline_pct": ("higher", 0.07),
    # continuous-batching goodput (bench.py --serving; nxdi_tpu/serving).
    # One-sided like everything else, and silently skipped against older
    # trajectory files that predate the serving engine (missing on a side).
    # Tail latencies get wider tolerances: p95s under a Poisson workload
    # are the noisiest numbers the bench emits.
    "serving_goodput_req_s": ("higher", 0.07),
    "serving_tok_s": ("higher", 0.07),
    "serving_ttft_p50_ms": ("lower", 0.10),
    "serving_ttft_p95_ms": ("lower", 0.15),
    "serving_tpot_p50_ms": ("lower", 0.07),
    "serving_tpot_p95_ms": ("lower", 0.12),
    # SLO-conditioned headline pair (PR: flight recorder + SLO monitor).
    # Same skip-vs-older-baselines behavior as the serving_* fields.
    # Attainment is a share of requests: near 100% the relative tolerance
    # is effectively absolute; goodput_slo inherits the tail-latency noise
    # (one extra breaching request moves it by a whole request's tokens).
    "slo_attainment_pct": ("higher", 0.05),
    "goodput_slo_tok_s": ("higher", 0.10),
    # fleet-mode headline fields (bench.py --serving --replicas N; PR:
    # fleet observatory). One-sided, skipped against pre-fleet baselines
    # (missing on a side). The straggler gap measures cross-replica spread
    # on a host-contended run — the noisiest fleet number, so it gets the
    # widest tolerance; attainment behaves like its single-replica twin.
    "fleet_goodput_req_s": ("higher", 0.07),
    "fleet_tok_s": ("higher", 0.07),
    "fleet_straggler_gap_pct": ("lower", 0.30),
    "fleet_slo_attainment_pct": ("higher", 0.05),
    "fleet_goodput_slo_tok_s": ("higher", 0.10),
    # routed-mode headline fields (bench.py --serving --replicas N --routed;
    # PR: replica router). One-sided, skipped against pre-router baselines
    # (missing on a side). TTFTs are CLIENT-observed through the HTTP
    # frontend + stream polling, so they carry the most scheduling AND
    # network noise of any latency the bench emits — widest tolerances.
    "routed_goodput_req_s": ("higher", 0.07),
    "routed_tok_s": ("higher", 0.07),
    "routed_ttft_p50_ms": ("lower", 0.12),
    "routed_ttft_p95_ms": ("lower", 0.18),
    # chaos-mode recovery latency (bench.py --serving --chaos; PR: chaos
    # harness). One-sided, skipped against pre-chaos baselines (missing
    # on a side). Requeue -> re-admission latency rides the scheduler's
    # admission cadence under a faulted Poisson workload — noisy, so it
    # gets a wide tolerance; the retention headline is ABSOLUTE-gated
    # below instead (a ratio of two same-run passes needs no baseline).
    "chaos_recovery_p95_ms": ("lower", 0.30),
    # mixed-dispatch headline fields (bench.py --serving --mixed-dispatch;
    # PR: unified mixed prefill+decode dispatch). One-sided, skipped
    # against pre-mixed baselines (missing on a side). Padding waste is a
    # packing-efficiency share of dispatched tokens: it regresses when the
    # token-bucket ladder or the packer fragments, and gets a wider
    # tolerance than goodput because one awkward arrival pattern can shift
    # a bucket rung.
    "mixed_goodput_tok_s": ("higher", 0.07),
    "mixed_padding_waste_pct": ("lower", 0.15),
    # prefix-cache headline pair (bench.py --serving --prefix-cache;
    # PR: radix prefix cache). One-sided, skipped against pre-prefix
    # baselines (missing on a side). The hit rate on the shared-prefix
    # bench workload is near-deterministic (every request after the first
    # shares the prompt head), so it gets a tight tolerance: a drop means
    # the radix match or the retire-insert path broke, not noise. Goodput
    # inherits the usual serving scheduling noise.
    "prefix_hit_rate_pct": ("higher", 0.02),
    "prefix_goodput_tok_s": ("higher", 0.07),
    # disaggregated-serving headline triple (bench.py --serving
    # --disaggregated; PR: prefill/decode disaggregation). One-sided,
    # skipped against pre-disagg baselines (missing on a side). The p95
    # TPOT is the disaggregation claim itself — decode steps freed from
    # prefill interference — and is CLIENT-observed through stream
    # polling, so it inherits the routed-tier noise; the handoff p50 is a
    # one-time per-request migration span (payload fetch -> decode-side
    # import -> retention ack) over localhost HTTP, the noisiest small
    # number here, so it gets the widest tolerance.
    "disagg_goodput_tok_s": ("higher", 0.07),
    "disagg_tpot_p95_ms": ("lower", 0.15),
    "disagg_handoff_p50_ms": ("lower", 0.30),
}

#: metric -> (direction, absolute limit) checked on the FRESH record alone —
#: no baseline needed (so a pre-sentinel trajectory cannot make the gate
#: vacuous) and trivially skipped when the field is absent. "lower" = the
#: fresh value must stay strictly under the limit.
#: sentinel_overhead_pct: the numerics sentinel (PR: numerics sentinel) is
#: an always-on correctness observatory; it may not cost 3% of the engine
#: step (bench.py --serving A/B smoke, ABBA-interleaved).
#: routed_failovers / routed_errors: the routed bench kills nothing (its
#: one drain is cooperative), so ANY failover or error-finished request is
#: a routing bug, not noise — must stay strictly under 1, fresh-side only.
#: chaos_goodput_retention_pct: the chaos bench's faulted pass vs its own
#: fault-free pass on identical work (bench.py --serving --chaos) — the
#: recovery machinery must preserve at least 70% of goodput under the
#: seeded fault plan, not merely avoid crashing. Higher-is-better floor.
#: trace_overhead_pct: distributed tracing fully on (sample rate 1.0,
#: every hop recorded) vs fully off, same routed mini-workload,
#: ABBA-interleaved (bench.py --serving --routed) — always-on tracing may
#: not cost 3% of routed wall.
#: trace_ttft_attribution_pct: median fraction of the CLIENT-observed
#: submit→first-token window that the assembled trace's critical path
#: accounts for — the attribution story must explain at least 90% of the
#: TTFT it claims to decompose, or the waterfall is decoration.
#: qos_slo_attainment_pct_interactive: the QoS control plane's reason to
#: exist — interactive-class SLO attainment on the mixed 3-class workload
#: must hold an absolute floor even with 2/3 of the load being background
#: classes; qos_fairness_jain: Jain's index over per-tenant served tokens
#: (1.0 = even) — the scheduler may not buy that floor by starving a
#: tenant.
ABSOLUTE_LIMITS: Dict[str, Tuple[str, float]] = {
    "sentinel_overhead_pct": ("lower", 3.0),
    "routed_failovers": ("lower", 1.0),
    "routed_errors": ("lower", 1.0),
    "chaos_goodput_retention_pct": ("higher", 70.0),
    "trace_overhead_pct": ("lower", 3.0),
    "trace_ttft_attribution_pct": ("higher", 90.0),
    "qos_slo_attainment_pct_interactive": ("higher", 80.0),
    "qos_fairness_jain": ("higher", 0.8),
}


def check_absolute(
    fresh: dict, limits: Dict[str, Tuple[str, float]],
) -> Tuple[List[dict], List[str]]:
    """``(rows, skipped)`` like :func:`compare`, against fixed limits."""
    rows, skipped = [], []
    for metric, (direction, limit) in limits.items():
        val = fresh.get(metric)
        if not isinstance(val, (int, float)):
            skipped.append(metric)
            continue
        worse = val >= limit if direction == "lower" else val <= limit
        rows.append({
            "metric": metric,
            "direction": direction,
            "baseline": None,
            "fresh": val,
            "limit": limit,
            "regression": bool(worse),
        })
    return rows, skipped


def default_baseline(root: str) -> Optional[str]:
    rounds = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    return rounds[-1] if rounds else None


def bench_record(d: dict) -> dict:
    """Unwrap a bench record: the BENCH_r*.json trajectory files store the
    bench.py JSON line under ``parsed`` (next to the driver's n/cmd/rc);
    fresh bench.py output is the record itself."""
    if "value" not in d and isinstance(d.get("parsed"), dict):
        return d["parsed"]
    return d


def compare(
    baseline: dict, fresh: dict, tolerances: Dict[str, Tuple[str, float]],
    scale: float = 1.0,
) -> Tuple[List[dict], List[str]]:
    """``(rows, skipped)``: one row per comparable metric with its verdict."""
    rows, skipped = [], []
    for metric, (direction, tol) in tolerances.items():
        base, new = baseline.get(metric), fresh.get(metric)
        if not isinstance(base, (int, float)) or not isinstance(new, (int, float)):
            skipped.append(metric)
            continue
        if base == 0:
            skipped.append(metric)
            continue
        delta = (new - base) / abs(base)
        worse = -delta if direction == "higher" else delta
        rows.append({
            "metric": metric,
            "direction": direction,
            "baseline": base,
            "fresh": new,
            "delta_pct": round(100.0 * delta, 2),
            "tolerance_pct": round(100.0 * tol * scale, 2),
            "regression": worse > tol * scale,
        })
    return rows, skipped


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python scripts/bench_gate.py",
        description="gate a fresh bench JSON against the BENCH_r*.json trajectory",
    )
    parser.add_argument("fresh", help="fresh bench.py output JSON (file path)")
    parser.add_argument("--baseline", default=None,
                        help="baseline JSON (default: newest BENCH_r*.json "
                             "next to this repo)")
    parser.add_argument("--tolerance-scale", type=float, default=1.0,
                        help="multiply every tolerance (e.g. 2.0 on a noisy "
                             "shared chip)")
    parser.add_argument("--json", dest="json_path", default=None,
                        help="write the comparison rows as JSON here")
    parser.add_argument("-q", "--quiet", action="store_true")
    args = parser.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    baseline_path = args.baseline or default_baseline(root)
    if baseline_path is None:
        print("bench_gate: no --baseline and no BENCH_r*.json found", file=sys.stderr)
        return 2
    try:
        with open(args.fresh) as f:
            fresh = bench_record(json.load(f))
        with open(baseline_path) as f:
            baseline = bench_record(json.load(f))
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_gate: {e}", file=sys.stderr)
        return 2

    tolerances = dict(TOLERANCES)
    if any(k in fresh for k in ("serving_goodput_req_s",
                                "fleet_goodput_req_s",
                                "routed_goodput_req_s",
                                "mixed_goodput_tok_s",
                                "prefix_goodput_tok_s",
                                "disagg_goodput_tok_s",
                                "chaos_goodput_retention_pct",
                                "qos_slo_attainment_pct_interactive",
                                "autoscale_cycle_ok")):
        # a serving-, fleet-, or routed-mode FRESH record duplicates its
        # "value" headline as serving_/fleet_/routed_goodput_req_s (which
        # carry their own tolerances), and against a decode-mode baseline
        # "value" (tok/s/chip) measures something else entirely — the
        # generic "value" row must not gate it. Keyed on the FRESH side
        # only: a decode-mode record must keep its headline gate even
        # against a trajectory baseline that folded serving_*/fleet_*
        # fields in (the side-file folding the docstring describes), or a
        # real tok/s regression would pass silently.
        tolerances.pop("value", None)
    rows, skipped = compare(baseline, fresh, tolerances, scale=args.tolerance_scale)
    abs_rows, abs_skipped = check_absolute(fresh, ABSOLUTE_LIMITS)
    rows += abs_rows
    skipped += abs_skipped
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump({"baseline": baseline_path, "rows": rows,
                       "skipped": skipped}, f, indent=2)

    regressions = [r for r in rows if r["regression"]]
    if not args.quiet:
        print(f"bench_gate: vs {os.path.basename(baseline_path)}", file=sys.stderr)
        for r in rows:
            mark = "REGRESSION" if r["regression"] else "ok"
            arrow = "^" if r["direction"] == "higher" else "v"
            if r.get("baseline") is None:  # absolute-limit row
                print(
                    f"  {r['metric']:<32} {arrow} {r['fresh']:>10g} "
                    f"(absolute limit {r['limit']:g})  {mark}",
                    file=sys.stderr,
                )
                continue
            print(
                f"  {r['metric']:<32} {arrow} {r['baseline']:>10g} -> "
                f"{r['fresh']:>10g}  {r['delta_pct']:+7.2f}% "
                f"(tol {r['tolerance_pct']:g}%)  {mark}",
                file=sys.stderr,
            )
        if skipped:
            print(f"  skipped (missing/null on a side): {', '.join(skipped)}",
                  file=sys.stderr)
        print(
            f"bench_gate: {len(rows)} compared, {len(regressions)} regressions",
            file=sys.stderr,
        )
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
