"""What decides ``correct``, and the two things the harness takes from the
app's own declarations (its cache tree, its application class), on the CPU at
toy sizes. Nothing here is a measurement.

The control (the reference over int8 weights in the program's place) and the
fault (a token altered where it is produced) are kept here as tests; the
benchmark's own runs never run them.
"""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark import cells, correctness, serving_app  # noqa: E402
import toy_routed_reference  # noqa: E402  (now: importing benchmark.run rewrites sys.path[0])
from toys import quiet_run, served_by as _served_by, toy_config as _toy, toy_steady_cell  # noqa: E402

BIG_SEED = 2**31 + 4321


# -- the cache the app declares, the class the family names ---------------------

def test_empty_cache_holds_whatever_tree_is_declared():
    """Three keys of unequal shapes and dtypes, one with a sharded axis: a paged
    latent pool beside a window ring and a state, say."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
    struct = {
        "latent": jax.ShapeDtypeStruct((3, 256, 1, 24), jnp.bfloat16),
        "k_win": jax.ShapeDtypeStruct((2, 4, 64, 2, 16), jnp.bfloat16),
        "state": jax.ShapeDtypeStruct((5, 4, 8), jnp.float32),
    }
    specs = {"latent": P(), "k_win": P(None, None, None, "mp", None), "state": P()}
    cache = serving_app.empty_cache(struct, specs, mesh)
    assert sorted(cache) == sorted(struct)
    for key, want in struct.items():
        assert cache[key].shape == want.shape and cache[key].dtype == want.dtype
        assert not np.asarray(cache[key], dtype=np.float32).any()
        assert cache[key].sharding.spec == specs[key]
    shard = cache["k_win"].addressable_shards[0].data.shape
    assert shard == (2, 4, 64, 1, 16)  # never whole on one device


def test_build_app_takes_the_familys_own_application_class(monkeypatch):
    from nxdi_tpu.models import registry
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    cfg = _toy("qwen2")
    plain = serving_app.build_app(cfg, [256], seed=1)
    assert type(plain).__mro__[1] is TpuModelForCausalLM

    class FamilyApp(TpuModelForCausalLM):
        def _cache_struct(self):
            struct = super()._cache_struct()
            struct["state"] = struct["k"]
            return struct

        def cache_partition_specs(self):
            specs = dict(super().cache_partition_specs())
            specs["state"] = specs["k"]
            return specs

    family, _ = registry.get_family("qwen2")
    monkeypatch.setattr(family, "APPLICATION_CLS", FamilyApp, raising=False)
    app = serving_app.build_app(cfg, [256], seed=1)
    assert isinstance(app, FamilyApp) and serving_app.application_class(family) is FamilyApp
    app._build_wrappers()  # gives the app its mesh
    cache = app.init_cache_host()
    assert sorted(cache) == ["k", "state", "v"] and cache["state"].shape == cache["k"].shape


# -- the comparison on synthetic logits ------------------------------------------

VOCAB, SEQ = 32, 24
ROUTED = dict(routing_margin=0.01, logit_tolerance_undecided=0.5, undecided_share_max=0.25)


def _synthetic(probe_off, served_off, margins, routed=True, **bench):
    """``check`` with a made-up reference whose logits are a fixed table by
    token id (top logit 4 at ``(id + 1) % VOCAB``): the probe is off by
    ``probe_off`` (position -> amount) and the served token at position
    ``first + i`` lies ``served_off[i]`` under the top. ``margins`` gives the
    positions (of either sequence) whose router margin is 0."""
    import jax.numpy as jnp

    table = np.full((VOCAB, VOCAB), 0.0, np.float32)
    for t in range(VOCAB):
        table[t, (t + 1) % VOCAB] = 4.0
        table[t, (t + 2) % VOCAB] = 4.0 - 0.125 * ((t % 8) + 1)  # runner-up: .125 .. 1 under

    def reference(params, config, ids):
        return jnp.asarray(table)[jnp.asarray(ids)]

    def routing_margins(params, config, ids):
        m = np.full(len(ids), 1.0, np.float32)
        m[[p for p in margins if p < len(ids)]] = 0.0
        return jnp.asarray(m)

    config = {"vocab_size": VOCAB, "benchmark": dict(
        logit_mse_tolerance=1.0, logit_tolerance=0.25, served_gap_tolerance=0.25, **(ROUTED if routed else {}), **bench)}
    seed = 5
    prompt = correctness.probe_prompt(seed, VOCAB)
    got = table[prompt].copy()
    for pos, off in probe_off.items():
        got[pos, 3] += off
    request = list(range(8))  # its successors are 8, 9, ...: greedy by the table
    tokens = []
    for i in range(SEQ):
        prev = (request + tokens)[-1]
        off = served_off.get(i, 0.0)
        tokens.append((prev + 1) % VOCAB if off == 0.0 else (prev + 2) % VOCAB)
        if off:
            assert table[prev, tokens[-1]] == pytest.approx(4.0 - off), "pick a position whose runner-up is that far"
    sample = SimpleNamespace(prompt=request, prompt_len=len(request), index=0, fault=None,
                             output=SimpleNamespace(token_ids=tokens))
    said = []
    out = correctness.check({}, config, reference, seed, got, [sample], said.append,
                            routing_margins=routing_margins if routed else None)
    return out, said


def _pos_with_runner_up(off):
    """An index i of the served tokens whose predecessor's runner-up lies
    ``off`` under the top, given that all earlier tokens are the top ones."""
    for i in range(SEQ):
        prev = 7 + i
        if 0.125 * ((prev % 8) + 1) == off:
            return i
    raise AssertionError(off)


CASES = {
    # name: (probe_off, served runner-up distance or None, undecided positions, routed, ok)
    "all_within_tolerance": ({5: 0.2}, 0.125, [], True, True),
    "over_only_where_undecided_and_within_the_looser": ({5: 0.4}, 0.375, "those", True, True),
    "over_at_one_decided_position": ({5: 0.4}, None, [6], True, False),
    "served_over_at_one_decided_position": ({}, 0.375, [], True, False),
    "over_the_looser_figure_where_undecided": ({5: 0.6}, None, [5], True, False),
    "undecided_share_over_the_cap": ({}, None, list(range(30)), True, False),
    "no_routing_margins_within": ({5: 0.2}, 0.125, [], False, True),
    "no_routing_margins_over": ({5: 0.4}, None, [], False, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_check_holds_decided_positions_tight_and_undecided_ones_loose(case):
    probe_off, served, undecided, routed, want = CASES[case]
    served_off = {}
    if served is not None:
        served_off[_pos_with_runner_up(served)] = served
    if undecided == "those":  # exactly the positions that are over the tight limit
        undecided = [5, len(range(8)) - 1 + _pos_with_runner_up(served)]
    out, said = _synthetic(probe_off, served_off, undecided, routed)
    assert out["ok"] is want, said
    names = list(out["compared"])
    assert names[:3] == ["probe_mse", "probe_diff", "served_gap"]
    assert (("undecided_share" in names) is routed) and (("probe_diff_undecided" in names) is routed)
    for c in out["compared"].values():
        assert sorted(c) == ["limit", "value"]
    if not routed:  # bit for bit what a reference without margins always got
        assert out["compared"]["probe_diff"]["value"] == pytest.approx(max(probe_off.values()))
        assert out["compared"]["served_gap"]["value"] == pytest.approx(served or 0.0)
    assert any("decided" in s and "undecided" in s for s in said)  # both counts are printed


def test_the_sample_has_the_longest_and_draws_the_rest_from_the_seed():
    def served(i, prompt_len, n, fault=None, out=True):
        return SimpleNamespace(index=i, prompt_len=prompt_len, fault=fault,
                               output=SimpleNamespace(token_ids=[1] * n) if out else None)

    pool = [served(i, 10 + i, 20) for i in range(40)] + [served(40, 500, 30), served(41, 900, 50, fault="x"),
                                                          served(42, 900, 50, out=False)]
    a = correctness.sample_served(pool, 7, tokens=200)
    b = correctness.sample_served(pool, 7, tokens=200)
    c = correctness.sample_served(pool, BIG_SEED, tokens=200)
    assert a[0].index == 40 and c[0].index == 40  # the longest clean one, always
    assert [s.index for s in a] == [s.index for s in b] != [s.index for s in c]
    assert sum(len(s.output.token_ids) for s in a) >= 200 and len(a) == 10
    assert all(s.fault is None and s.output is not None for s in a + c)
    assert len(correctness.sample_served(pool, 7, tokens=10**6)) == correctness.SAMPLE_REQUESTS[1]
    assert len(correctness.sample_served(pool, 7, tokens=1)) == correctness.SAMPLE_REQUESTS[0]
    assert correctness.sample_served([pool[-1]], 7) == []
    ids = correctness.padded(list(range(1, 300)))
    assert len(ids) == 512 and ids[298] == 299 and not ids[299:].any()


# -- the app against the reference, the control, the wrong router ------------------

@pytest.fixture(scope="module")
def routed():
    """A 2-layer Mixtral of 8 experts, top 2, through ``build_app`` and the
    engine on the paged path, with what it served."""
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    reference = toy_routed_reference
    cfg = _toy("mixtral", num_local_experts=8, num_experts_per_tok=2)
    # the three figures, from this toy's own readings on the CPU (seeds 11-13: positions where no
    # expert is swapped read 0.003, one swapped expert 0.05-0.06, the wrong router 0.07 at EVERY
    # position; a router probability moves by ~1e-4 under bf16, 5-10 % of the margins lie under 5e-4)
    cfg["benchmark"].update(routing_margin=5e-4, logit_tolerance=0.01, served_gap_tolerance=0.01,
                            logit_tolerance_undecided=0.1, undecided_share_max=0.2)
    seed = 12  # a seed on which an expert IS swapped somewhere
    app = serving_app.build_app(cfg, [256], seed=seed)
    app.load()
    engine = InferenceEngine(app, SchedulerConfig(num_slots=4))
    samples = correctness.sample_served(_served_by(engine, seed), seed, tokens=200)
    got = correctness.program_probe(app, correctness.probe_prompt(seed, 256), 256)
    return SimpleNamespace(cfg=cfg, seed=seed, app=app, samples=samples, got=got, reference=reference)


def test_a_routed_family_is_served_and_checked_by_its_margins(routed):
    r, said = routed, []
    out = correctness.check(r.app.params, r.cfg, r.reference.forward, r.seed, r.got, r.samples,
                            said.append, routing_margins=r.reference.routing_margins)
    assert out["ok"], said
    assert 0.0 < out["compared"]["undecided_share"]["value"] < 0.2  # some routers ARE undecided here
    assert out["served_tokens"] >= 200


def test_a_reference_whose_router_is_wrong_fails_at_the_decided_positions(routed):
    r, said = routed, []
    out = correctness.check(r.app.params, r.cfg, r.reference.forward_without_renormalisation, r.seed,
                            r.got, r.samples, said.append,
                            routing_margins=r.reference.routing_margins)
    assert not out["ok"], said
    c = out["compared"]["probe_diff"]
    assert c["value"] > 3 * c["limit"]  # not a near miss: the decided majority is far off


def test_a_routed_family_held_to_one_tight_tolerance_would_fail(routed):
    """Why the margins exist: without them this very app reads incorrect (or
    needs a tolerance that passes the wrong router's neighbours)."""
    r, said = routed, []
    out = correctness.check(r.app.params, r.cfg, r.reference.forward, r.seed, r.got, r.samples, said.append)
    assert list(out["compared"]) == ["probe_mse", "probe_diff", "served_gap"]
    assert not out["ok"], said


@pytest.fixture(scope="module")
def dense():
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig

    cfg = _toy("qwen2", hidden_size=256, intermediate_size=512, num_hidden_layers=6)
    cfg["benchmark"].update(DENSE_LIMITS)
    seed = BIG_SEED
    app = serving_app.build_app(cfg, [256], seed=seed)
    app.load()
    engine = InferenceEngine(app, SchedulerConfig(num_slots=4))
    samples = correctness.sample_served(_served_by(engine, seed), seed, tokens=200)
    got = correctness.program_probe(app, correctness.probe_prompt(seed, 256), 256)
    return SimpleNamespace(cfg=cfg, seed=seed, app=app, samples=samples, got=got,
                           reference=cells.load_plugin("reference", "dense_decoder"))


#: this toy's limits, from its readings on the CPU: the bf16 program's mean squared logit
#: difference reads 3.5e-6 (rms 0.0019), the int8 control's 1.7e-5 (rms 0.0041)
DENSE_LIMITS = dict(logit_mse_tolerance=8e-6, logit_tolerance=0.05, served_gap_tolerance=0.05)


def test_the_program_passes_and_the_int8_control_fails(dense):
    """The control, kept as a test at a size a test run can hold: the reference
    over int8 weights in the program's place has to read incorrect where the
    bf16 program reads correct."""
    import jax

    from nxdi_tpu.parallel.layers import sharding_tree

    d, said = dense, []
    program = correctness.check(d.app.params, d.cfg, d.reference, d.seed, d.got, d.samples, said.append)
    assert program["ok"], said
    lower = correctness.int8_weights(jax.tree_util.tree_map(lambda a: a.copy(), d.app.params))
    control = correctness.control_tokens(d.reference, lower, d.cfg,
                                         correctness.probe_prompt(d.seed, 256), d.samples)
    again = serving_app.seeded_params(d.app.build_params_struct(),
                                      sharding_tree(d.app.param_specs(), d.app.mesh), d.seed)
    for a, b in zip(jax.tree_util.tree_leaves(again), jax.tree_util.tree_leaves(d.app.params)):
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()  # the seed gives the weights back
    lowered = correctness.check(again, d.cfg, d.reference, d.seed, d.got, d.samples, said.append,
                                control=control)
    assert not lowered["ok"], said
    mse = lowered["compared"]["probe_mse"]
    assert mse["value"] > mse["limit"] > program["compared"]["probe_mse"]["value"]


# -- the rest of a run with the timed path broken underneath -----------------------

@pytest.mark.parametrize("fault", ["none", "a_token_altered_where_it_is_produced"])
def test_run_cell_reads_a_broken_timed_path_as_incorrect(monkeypatch, fault):
    """Skips the look for a chip and drives the rest of a run. Broken: every
    fifth batch of tokens the engine fetches from the device has each id moved
    by one, so the streams are self-consistent and only the reference can tell."""
    import jax

    from benchmark import run as bench_run
    from nxdi_tpu.serving import InferenceEngine

    quiet_run(monkeypatch)
    if fault != "none":
        fetch, calls = InferenceEngine._tokens_of, [0]

        def altered(self, out):
            toks = np.asarray(fetch(self, out))
            calls[0] += 1
            return (toks + 1) % 256 if calls[0] % 5 == 0 else toks

        monkeypatch.setattr(InferenceEngine, "_tokens_of", altered)
    cell = toy_steady_cell(_toy("qwen2"))
    said = []
    line = bench_run.run_cell(cell, BIG_SEED, 3.0, False, jax.devices()[:1], said.append)
    assert line["failed"] == 0 and line["attempted"] == 9, said
    assert line["correct"] is (fault == "none"), said
    gap = line["compared"]["served_gap"]
    assert (gap["value"] > gap["limit"]) is (fault != "none")
