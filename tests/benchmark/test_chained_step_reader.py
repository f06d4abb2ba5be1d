"""``engine.chained_step_pct`` (PR 33) on hand-made step records, and on a toy
share of the routed model served by an engine that keeps the chain: the held
pairs of every decode step arrive with that step's collect, a step later, and
land in the record of the step that dispatched them."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import cells, records, serving_app  # noqa: E402
from test_pangu_cell import toy_share  # noqa: E402
from toys import served_by  # noqa: E402


def _read(run):
    return cells.load_plugin("per_layer", "engine.chained_step_pct")(run)


def _run(steps):
    return records.RunRecords(
        seconds=10.0, t_open=0.0, t_close=10.0, t_host_end=10.0, setup_s=5.0, served=[],
        population=[], tokens_in_window=0, steps=steps, counters={}, slots=4, pool_blocks=100,
        block_size=128, tp=1, config={}, traffic={}, device_kind="x",
    )


def _step(rows=4, prefills=0, **fields):
    return SimpleNamespace(
        t_start=0.0, t_end=0.02, prefills=[{}] * prefills,
        decode={"rows": [{"slot": i, "request_id": i} for i in range(rows)]} if rows else None,
        **fields,
    )


@pytest.mark.parametrize("flags, want", [
    ([True, True, True, True], 100.0),
    ([False, False, False], 0.0),
    ([True, False, True, True], 75.0),
], ids=["all", "none", "three-of-four"])
def test_the_share_of_the_decode_only_steps_that_were_chained(flags, want):
    steps = [_step(chained=c) for c in flags]
    steps.append(_step(prefills=1, chained=False))  # a prefill rode along: not a decode-only step
    steps.append(_step(rows=0, chained=False))  # collected only, dispatched nothing
    assert _read(_run(steps)) == pytest.approx(want)


def test_records_without_the_field_read_nothing():
    assert _read(_run([_step(), _step()])) is None  # the parent's records
    assert _read(_run([])) is None
    assert _read(_run([_step(prefills=1, chained=True)])) is None  # no decode-only step


def test_the_held_pairs_land_in_the_dispatching_step_with_the_chain_on():
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    app = serving_app.build_app(toy_share(), [256], seed=3)
    app.load()
    engine = InferenceEngine(app, SchedulerConfig(num_slots=4))
    served_by(engine, 3, requests=5, new=12)
    recs = engine.flight.snapshot_records()
    decode = [r for r in recs if r.decode is not None]
    chained = [r for r in decode if r.chained]
    assert chained and len(chained) < len(decode)  # a finish by length is collected first
    assert all(r.moe_held_pairs is not None and r.moe_routed_layers == 2 for r in decode)
    assert all(r.decode["tokens_emitted"] == len(r.decode["rows"]) for r in decode)
    assert all(r.moe_held_pairs is None for r in recs if r.decode is None)
    registry = app.telemetry.registry
    assert registry.get("nxdi_moe_held_pairs_total").value() == sum(r.moe_held_pairs for r in decode)
    assert registry.get("nxdi_decode_chained_steps_total").value() == len(chained)
    assert registry.get("nxdi_decode_overrun_tokens_total").value() == 0
    pct = _read(_run(recs))
    only = [r for r in decode if not r.prefills]
    assert pct == pytest.approx(100.0 * sum(r.chained for r in only) / len(only)) and pct > 50
