"""Deterministic fault injection + dispatch watchdog
(nxdi_tpu/runtime/faults) — pure host-side logic, no model required.

Property anchors (ISSUE 14):
- a FaultPlan is a deterministic schedule: same seed -> same firing
  pattern in any process (crc32-seeded per-rule streams, never the
  salted builtin hash), and exhausted probabilistic rules still consume
  their stream so later schedules never depend on limits;
- the classifier maps REAL backend exception types (live XlaRuntimeError
  instances included) onto the three-kind taxonomy, defaulting unknown
  failures to fatal;
- watchdog timeouts derive from CostSheet floors (floor x multiplier,
  clamped to a minimum; analytic fallback sheets count), retries are
  transient-only with a deterministic backoff schedule, and a timed-out
  dispatch abandons its worker and counts a trip;
- unarmed failpoint sites are a bare attribute test — an ABBA-interleaved
  micro-smoke pins their cost under 1% of a small dispatch-sized body.
"""

import threading
import time

import numpy as np
import pytest

from nxdi_tpu.runtime import faults
from nxdi_tpu.runtime.faults import (
    DispatchWatchdog,
    FatalModelError,
    FaultPlan,
    FaultRule,
    ResourceExhausted,
    TransientDispatchError,
    classify,
    jittered_backoff,
)


# ------------------------------------------------------------------ taxonomy
def _xla_error(msg):
    # a REAL jaxlib runtime error instance, as the dispatch path raises it
    from jax.errors import JaxRuntimeError

    return JaxRuntimeError(msg)


def test_classify_taxonomy_classes_are_fixed_points():
    assert classify(TransientDispatchError("x")) == "transient"
    assert classify(ResourceExhausted("x")) == "exhausted"
    assert classify(FatalModelError("x")) == "fatal"
    # the taxonomy rides RuntimeError so existing `except RuntimeError`
    # preemption paths absorb an injected exhaustion without edits
    assert issubclass(ResourceExhausted, RuntimeError)
    assert issubclass(TransientDispatchError, RuntimeError)
    assert issubclass(FatalModelError, RuntimeError)


def test_classify_stdlib_exception_types():
    assert classify(TimeoutError("t")) == "transient"
    assert classify(ConnectionError("refused")) == "transient"
    assert classify(BrokenPipeError()) == "transient"
    assert classify(OSError("socket closed")) == "transient"  # transport tier
    assert classify(MemoryError()) == "exhausted"
    # unknown exceptions default to fatal: retrying an unclassified
    # failure risks corrupting state for no proven benefit
    assert classify(ValueError("bad shape")) == "fatal"
    assert classify(KeyError("missing")) == "fatal"


def test_classify_real_xla_runtime_errors_by_status_phrase():
    e = _xla_error("RESOURCE_EXHAUSTED: Out of memory allocating 2.1G")
    assert type(e).__name__ == "JaxRuntimeError"  # the real class, not a fake
    assert classify(e) == "exhausted"
    assert classify(_xla_error("DEADLINE_EXCEEDED: slow collective")) == "transient"
    assert classify(_xla_error("UNAVAILABLE: channel reset")) == "transient"
    assert classify(_xla_error("ABORTED: preempted")) == "transient"
    assert classify(_xla_error("INVALID_ARGUMENT: shape mismatch")) == "fatal"
    assert classify(_xla_error("INTERNAL: compiler bug")) == "fatal"


def test_classify_stale_buffer_donation_race_is_transient():
    """A deleted/donated-buffer error is the signature of a
    watchdog-abandoned launch racing its retry under donation: the
    survivor leaves model state coherent, so a fresh replay succeeds —
    transient, never fatal."""
    assert classify(RuntimeError(
        "Array has been deleted with shape=float32[4,256,2,16]."
    )) == "transient"
    assert classify(_xla_error(
        "INVALID_ARGUMENT: buffer has been deleted or donated"
    )) == "transient"


def test_classify_block_pool_exhaustion_message():
    # the BlockSpaceManager's real dry-pool error is a plain RuntimeError
    e = RuntimeError("KV block pool exhausted (32 blocks); free a sequence")
    assert classify(e) == "exhausted"
    assert classify(RuntimeError("something else broke")) == "fatal"


def test_make_error_kinds():
    assert isinstance(faults.make_error("transient", "x"), TransientDispatchError)
    assert isinstance(faults.make_error("exhausted", "x"), ResourceExhausted)
    assert isinstance(faults.make_error("fatal", "x"), FatalModelError)
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.make_error("latency", "x")


# ------------------------------------------------------------------ rules
def test_fault_rule_validation_and_roundtrip():
    with pytest.raises(ValueError, match="trigger"):
        FaultRule("s", "sometimes")
    with pytest.raises(ValueError, match="kind"):
        FaultRule("s", kind="weird")
    with pytest.raises(ValueError, match="n >= 1"):
        FaultRule("s", "every", n=0)
    with pytest.raises(ValueError, match="0 <= p <= 1"):
        FaultRule("s", "prob", p=1.5)
    r = FaultRule("dispatch.*", "prob", p=0.25, kind="exhausted", limit=3)
    r2 = FaultRule.from_dict(r.to_dict())
    assert r2.to_dict() == r.to_dict()


def test_nth_and_every_triggers():
    plan = FaultPlan([
        FaultRule("a", "nth", n=3, kind="transient"),
        FaultRule("b", "every", n=2, kind="exhausted", limit=2),
    ])
    for i in range(1, 6):
        if i == 3:
            with pytest.raises(TransientDispatchError):
                plan.hit("a")
        else:
            assert plan.hit("a") is None
    fired = []
    for i in range(1, 8):
        try:
            plan.hit("b")
            fired.append(False)
        except ResourceExhausted:
            fired.append(True)
    # every 2nd hit, capped by limit=2: hits 2 and 4 fire, 6 does not
    assert fired == [False, True, False, True, False, False, False]
    assert plan.hits["b"] == 7 and plan.fired["b"] == 2
    assert plan.injected_total() == 3


def test_prob_trigger_is_seed_deterministic_across_plans():
    def pattern(seed):
        plan = FaultPlan([FaultRule("s", "prob", p=0.3, limit=0)], seed=seed)
        out = []
        for _ in range(64):
            try:
                plan.hit("s")
                out.append(0)
            except TransientDispatchError:
                out.append(1)
        return out

    a, b = pattern(7), pattern(7)
    assert a == b  # identical plans replay identically (no process salt)
    assert pattern(8) != a  # and the seed actually matters
    assert 2 < sum(a) < 40  # p=0.3 over 64 hits: sane, not degenerate


def test_exhausted_prob_rule_still_consumes_its_stream():
    """A limit-capped prob rule keeps drawing after exhaustion, so its
    stream position depends only on the hit count — never on how many
    fires the limit allowed.  Two plans differing only in ``limit`` sit
    at the same stream position after the same number of hits."""
    def mk(limit):
        return FaultPlan(
            [FaultRule("s", "prob", p=0.9, kind="latency", delay_s=0.0,
                       limit=limit)],
            seed=3)

    capped, uncapped = mk(1), mk(0)
    for _ in range(20):
        capped.hit("s")
        uncapped.hit("s")
    assert capped._rule_fired[0] == 1  # the cap held
    assert uncapped._rule_fired[0] > 1  # p=0.9 over 20 hits fires often
    # one draw per hit, fired or suppressed: the next draw agrees
    assert capped._rngs[0].random() == uncapped._rngs[0].random()


def test_site_patterns_fnmatch():
    plan = FaultPlan([FaultRule("dispatch.*", "every", n=1, limit=0)])
    with pytest.raises(TransientDispatchError):
        plan.hit("dispatch.forward")
    assert plan.hit("block.alloc") is None  # pattern does not match


def test_latency_kind_sleeps_and_reports():
    naps = []
    plan = FaultPlan([FaultRule("s", "nth", n=1, kind="latency", delay_s=0.5)])
    plan._sleep = naps.append
    assert plan.hit("s") == "latency"
    assert naps == [0.5]
    assert plan.hit("s") is None  # limit=1 default


def test_plan_serialization_roundtrip_and_arm_with_dict():
    plan = FaultPlan([FaultRule("a", "nth", n=2)], seed=11)
    clone = FaultPlan.from_dict(plan.to_dict())
    assert clone.seed == 11 and clone.rules[0].to_dict() == plan.rules[0].to_dict()
    try:
        armed = faults.arm(plan.to_dict())  # arm() accepts the dict form
        assert isinstance(armed, FaultPlan)
        assert faults.ACTIVE_PLAN is armed
    finally:
        faults.disarm()
    assert faults.ACTIVE_PLAN is None


def test_armed_context_restores_previous_plan():
    outer = FaultPlan(seed=1)
    inner = FaultPlan(seed=2)
    with faults.armed(outer):
        assert faults.ACTIVE_PLAN is outer
        with faults.armed(inner):
            assert faults.ACTIVE_PLAN is inner
        assert faults.ACTIVE_PLAN is outer  # restored, not cleared
    assert faults.ACTIVE_PLAN is None


def test_fire_counts_into_labelled_counter():
    from nxdi_tpu.telemetry import Telemetry

    tel = Telemetry(detail="basic")
    plan = FaultPlan([FaultRule("s", "every", n=1, limit=0)])
    with faults.armed(plan):
        with pytest.raises(TransientDispatchError):
            faults.fire("s", tel)
        with pytest.raises(TransientDispatchError):
            faults.fire("s", tel)
    ctr = tel.registry.counter("nxdi_fault_injected_total", "", ("site",))
    assert ctr.value(site="s") == 2.0


def test_plan_hit_is_thread_safe():
    plan = FaultPlan([FaultRule("s", "every", n=10, limit=0)])
    errs = []

    def worker():
        for _ in range(500):
            try:
                plan.hit("s")
            except TransientDispatchError:
                errs.append(1)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # 2000 hits, every 10th fires: exactly 200 — no lost updates
    assert plan.hits["s"] == 2000 and len(errs) == 200


# ------------------------------------------------------------------ backoff
def test_jittered_backoff_deterministic_core_and_cap():
    assert jittered_backoff(0, base_s=0.05, max_s=2.0) == 0.05
    assert jittered_backoff(3, base_s=0.05, max_s=2.0) == 0.4
    assert jittered_backoff(10, base_s=0.05, max_s=2.0) == 2.0  # capped


def test_jittered_backoff_jitter_bounds_and_determinism():
    import random

    a = [jittered_backoff(2, base_s=0.1, max_s=5.0, rng=random.Random(4))
         for _ in range(1)]
    b = [jittered_backoff(2, base_s=0.1, max_s=5.0, rng=random.Random(4))
         for _ in range(1)]
    assert a == b  # same rng seed -> same delay
    rng = random.Random(0)
    for _ in range(100):
        d = jittered_backoff(2, base_s=0.1, max_s=5.0, rng=rng, jitter=0.5)
        assert 0.2 <= d <= 0.4  # in [1 - jitter, 1] x base*2^2


# ------------------------------------------------------------------ watchdog
def test_watchdog_timeout_derivation_from_floors():
    wd = DispatchWatchdog(multiplier=20.0, min_timeout_s=0.5)
    # unknown tag: bare minimum
    assert wd.timeout_for("tkg") == 0.5
    # floor x multiplier once it clears the clamp
    wd.set_floor("tkg", 0.05, source="xla")
    assert wd.timeout_for("tkg") == pytest.approx(1.0)
    # a tiny floor stays clamped at the minimum
    wd.set_floor("cte", 0.001, source="analytic")
    assert wd.timeout_for("cte") == 0.5
    # set_floor keeps the MAX across buckets (the widest bucket bounds
    # every dispatch of the tag) and its source
    wd.set_floor("tkg", 0.02, source="analytic")
    assert wd.floors["tkg"] == 0.05 and wd.floor_sources["tkg"] == "xla"


def test_watchdog_load_floors_reads_cost_sheets(monkeypatch):
    """Floors come from the cost observatory — XLA-measured when
    available, the analytic fallback otherwise — keeping the max floor
    per tag across buckets."""
    class Sheet:
        def __init__(self, tag, floor_s, source):
            self.tag, self.floor_s, self.source = tag, floor_s, source

    from nxdi_tpu.analysis import costs

    monkeypatch.setattr(costs, "cost_sheets", lambda app, **kw: [
        Sheet("token_generation", 0.004, "xla"),
        Sheet("token_generation", 0.009, "analytic"),  # wider bucket wins
        Sheet("context_encoding", 0.030, "analytic"),
    ])
    wd = DispatchWatchdog(multiplier=10.0, min_timeout_s=0.01)
    assert wd.load_floors(app=object()) == 3
    assert wd.floors["token_generation"] == pytest.approx(0.009)
    assert wd.floor_sources["token_generation"] == "analytic"
    assert wd.timeout_for("context_encoding") == pytest.approx(0.3)


def test_watchdog_load_floors_swallows_analysis_failure(monkeypatch):
    from nxdi_tpu.analysis import costs

    def boom(app, **kw):
        raise RuntimeError("no compiled programs")

    monkeypatch.setattr(costs, "cost_sheets", boom)
    wd = DispatchWatchdog()
    assert wd.load_floors(app=object()) == 0
    assert wd.floors == {}  # defaults intact; min_timeout still applies


def test_watchdog_backoff_schedule_is_deterministic():
    wd = DispatchWatchdog(backoff_base_s=0.05, backoff_max_s=0.3)
    assert [wd.backoff_schedule(a) for a in range(4)] == [
        0.05, 0.1, 0.2, 0.3,  # doubled then capped
    ]


def test_watchdog_retries_transients_then_succeeds():
    naps, retries = [], []
    wd = DispatchWatchdog(max_retries=2, backoff_base_s=0.01,
                          backoff_max_s=1.0, min_timeout_s=5.0,
                          on_retry=lambda: retries.append(1),
                          sleep=naps.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientDispatchError("hiccup")
        return "ok"

    assert wd.run("tkg", flaky) == "ok"
    assert calls["n"] == 3 and wd.retries == 2 and len(retries) == 2
    assert naps == [0.01, 0.02]  # the deterministic schedule, attempt order
    wd.shutdown()


def test_watchdog_raises_after_retry_budget():
    wd = DispatchWatchdog(max_retries=1, min_timeout_s=5.0, sleep=lambda s: None)
    with pytest.raises(TransientDispatchError):
        wd.run("tkg", lambda: (_ for _ in ()).throw(
            TransientDispatchError("always")))
    assert wd.retries == 1
    wd.shutdown()


def test_watchdog_does_not_retry_fatal_or_exhausted():
    wd = DispatchWatchdog(max_retries=3, min_timeout_s=5.0, sleep=lambda s: None)
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise FatalModelError("shape mismatch")

    with pytest.raises(FatalModelError):
        wd.run("tkg", fatal)
    assert calls["n"] == 1 and wd.retries == 0  # no blind re-execution

    def dry():
        calls["n"] += 1
        raise ResourceExhausted("pool dry")

    with pytest.raises(ResourceExhausted):
        wd.run("tkg", dry)
    assert calls["n"] == 2  # exhausted propagates for preempt-and-retry
    wd.shutdown()


def test_watchdog_trip_abandons_worker_and_is_transient():
    trips = []
    wd = DispatchWatchdog(min_timeout_s=0.05, max_retries=0,
                          on_trip=lambda: trips.append(1),
                          sleep=lambda s: None)
    release = threading.Event()

    def wedged():
        release.wait(timeout=5.0)  # longer than the timeout

    with pytest.raises(TransientDispatchError, match="exceeded"):
        wd.run("tkg", wedged)
    assert wd.trips == 1 and trips == [1]
    assert wd._pool is None  # the wedged worker was abandoned
    release.set()
    # a fresh worker serves the next dispatch
    assert wd.run("tkg", lambda: 42) == 42
    wd.shutdown()


# ------------------------------------------------------- plan concurrency
def test_fault_plan_add_is_atomic_under_concurrent_hits():
    """PR-17 regression (concurrency auditor true positive): ``add`` grows
    the three parallel lists (rules/_rngs/_rule_fired) as ONE unit under
    the plan lock. Before the fix a ``hit`` racing an ``add`` could index
    a rule whose rng/fired slot did not exist yet (IndexError), or tear
    the seed derivation (len(self.rules) read mid-append)."""
    plan = FaultPlan(seed=7)
    stop = threading.Event()
    errors = []

    def hammer():
        while not stop.is_set():
            try:
                # prob p=0.0 matches every rule but never fires: each hit
                # walks ALL rules and consumes their rng streams — maximal
                # overlap with add()'s list growth
                plan.hit("dispatch.step")
            except Exception as e:  # pragma: no cover - the regression
                errors.append(e)
                return

    threads = [
        threading.Thread(target=hammer, daemon=True, name=f"nxdi-test-hit{i}")
        for i in range(2)
    ]
    for t in threads:
        t.start()
    for _ in range(300):
        plan.add(FaultRule("dispatch.*", "prob", p=0.0, limit=0))
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    assert not errors, errors
    assert (
        len(plan.rules) == len(plan._rngs) == len(plan._rule_fired) == 300
    )


# ---------------------------------------------------------- unarmed overhead
@pytest.mark.slow
def test_unarmed_site_guard_overhead_abba_smoke():
    """The unarmed failpoint guard (`faults.ACTIVE_PLAN is not None`) must
    cost under 1% of a dispatch-sized body. Slow-marked (tier-2): a pure
    wall-clock A/B smoke — the longest chaos-harness case in the tier-1
    run and the one most sensitive to suite load. ABBA-interleaved
    (guarded, bare, bare, guarded) so host warmup/jitter spreads across
    both sides; the body (a 512x512 matmul, tens of microseconds — still
    orders of magnitude below a real millisecond-scale dispatch) dwarfs
    the ~tens-of-nanoseconds attribute test."""
    assert faults.ACTIVE_PLAN is None
    a = np.random.default_rng(0).standard_normal((512, 512), dtype=np.float32)
    n = 50

    def bare():
        t0 = time.perf_counter()
        for _ in range(n):
            np.dot(a, a)
        return time.perf_counter() - t0

    def guarded():
        t0 = time.perf_counter()
        for _ in range(n):
            if faults.ACTIVE_PLAN is not None:
                faults.fire("dispatch.forward", None)
            np.dot(a, a)
        return time.perf_counter() - t0

    bare(), guarded()  # warm the BLAS path + bytecode before measuring
    # paired per-round ratios cancel slow drift (turbo, thermal, suite
    # load); the median of 12 rounds shrugs off scheduler spikes that a
    # sum-of-walls or min-of-rounds comparison inherits
    ratios = []
    for _ in range(12):
        g1, b1, b2, g2 = guarded(), bare(), bare(), guarded()
        ratios.append((g1 + g2) / (b1 + b2))
    ratios.sort()
    overhead_pct = 100.0 * (ratios[len(ratios) // 2] - 1.0)
    # generous ceiling for CI noise; the true guard cost is ~0.01%
    assert overhead_pct < 1.0, f"unarmed guard overhead {overhead_pct:.3f}%"
