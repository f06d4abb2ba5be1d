"""Program auditor (nxdi_tpu/analysis) over the llama CPU-mesh reference app.

Every checker gets BOTH directions:
  - negative: the shipped programs audit clean (no error findings),
  - positive: a deliberately seeded violation (undonated cache, policy with
    extra collectives, injected fp32 cast, closed-over weight, post-serving
    retrace, unmet kernel-strategy flag) is detected with an actionable
    message naming the submodel and bucket.

The audit path never loads weights (abstract structs, like aot_compile), so
these compile the same tiny programs the rest of tier-1 compiles.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nxdi_tpu.config import OnDeviceSamplingConfig
from nxdi_tpu.models.base import causal_lm_forward
from nxdi_tpu.models.llama import modeling_llama as ml
from nxdi_tpu.runtime.application import params_shape_struct
from nxdi_tpu.runtime.model_wrapper import (
    TAG_CONTEXT_ENCODING,
    TAG_TOKEN_GENERATION,
    ModelWrapper,
)


def make_app(**tpu_kwargs):
    """The SAME reference app the CLI audits (nxdi_tpu/cli/lint.py owns the
    definition — one source of truth for what tier-1 gates)."""
    from nxdi_tpu.cli.lint import build_reference_app

    defaults = dict(
        tp_degree=1,
        batch_size=1,
        seq_len=64,
        max_context_length=32,
        dtype="bfloat16",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True,
    )
    defaults.update(tpu_kwargs)
    return build_reference_app(defaults)


def seeded_wrapper(app, forward_fn, tag="seeded_model", **wrapper_kwargs):
    """A decode-shaped wrapper running ``forward_fn`` under the app's mesh and
    shardings — the vehicle for injecting violations into a real program."""
    from nxdi_tpu.parallel.layers import sharding_tree
    from nxdi_tpu.parallel.mesh import mesh_from_config

    app._build_wrappers()
    arch = ml.build_arch(app.config)
    w = ModelWrapper(
        tag,
        app.config,
        arch,
        ml.build_inv_freq(app.config),
        batch_size=1,
        n_active_tokens=1,
        buckets=[app.tpu_config.seq_len],
        attend_to_cache=True,
        forward_fn=forward_fn,
        forward_kwargs=dict(app.models[TAG_TOKEN_GENERATION].forward_kwargs),
        **wrapper_kwargs,
    )
    mesh = app.mesh or mesh_from_config(app.tpu_config)
    w.build(
        mesh,
        sharding_tree(app.param_specs(), mesh),
        sharding_tree(app.cache_partition_specs(), mesh),
    )
    return w


def audit_seeded(app, w):
    from nxdi_tpu.analysis import audit_wrapper

    return audit_wrapper(
        w, app.build_params_struct(), app._cache_struct(), config=app.config
    )


def errors_of(reports, checker):
    return [
        f
        for r in (reports if isinstance(reports, list) else reports.programs)
        for f in r.findings
        if f.checker == checker and f.severity == "error"
    ]


# ---------------------------------------------------------------------------
# clean path: the reference app (the CLI acceptance run) audits clean
# ---------------------------------------------------------------------------

def test_cli_lint_reference_app_clean(tmp_path):
    """`python -m nxdi_tpu.cli.lint` exits 0 on all compiled submodels of the
    llama CPU-mesh reference app — the tier-1 wiring of the audit."""
    from nxdi_tpu.cli.lint import main

    out = tmp_path / "report.json"
    rc = main([
        "--reference-app",
        "--tp-degree", "8",
        "--decode-steps-per-dispatch", "2",
        "--json", str(out),
        "-q",
    ])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["ok"] is True
    tags = {p["submodel"] for p in report["programs"]}
    assert tags == {
        TAG_CONTEXT_ENCODING, TAG_TOKEN_GENERATION, "tkg_multistep",
    }
    for p in report["programs"]:
        assert p["findings"] == [], p
        # both KV stacks donated in every program
        assert p["donated_cache_inputs"] == p["cache_inputs"] == 2
        # collectives within the policy budget
        for op, n in p["collectives"].items():
            assert n <= p["collective_budget"][op], (p["program"], op)


def test_audit_application_clean_tp1():
    report = make_app().audit()
    assert report.ok()
    assert report.errors() == []
    # tp=1: a single-device mesh budgets ZERO collectives, and the compiled
    # programs indeed have none
    for p in report.programs:
        assert all(n == 0 for n in p.collectives.values()), p.label


# ---------------------------------------------------------------------------
# seeded violations, one per checker
# ---------------------------------------------------------------------------

def test_donation_violation_detected(monkeypatch):
    """Programs compiled WITHOUT cache donation are flagged per cache leaf."""
    orig_jit = jax.jit

    def jit_without_donation(*args, **kwargs):
        kwargs.pop("donate_argnums", None)
        return orig_jit(*args, **kwargs)

    monkeypatch.setattr(jax, "jit", jit_without_donation)
    app = make_app()
    report = app.audit(submodels=[TAG_TOKEN_GENERATION])
    findings = errors_of(report, "donation")
    assert len(findings) == 2  # k and v
    msg = " | ".join(f.message for f in findings)
    assert "'k'" in msg and "'v'" in msg
    assert all(f.program == "token_generation_model[64]" for f in findings)


def test_collective_budget_violation_detected(monkeypatch):
    """A sharding-policy typo (decode stream suddenly S-sharded over the mp
    axis) inserts unbudgeted collectives — caught against the config-derived
    budget, which does NOT follow the buggy policy."""
    import nxdi_tpu.parallel.policy as pol

    def typo_policy(tc):
        from jax.sharding import PartitionSpec as P

        return pol.ShardingPolicy(hidden=P(None, pol.AXIS_MP, None))

    monkeypatch.setattr(pol, "token_generation_policy", typo_policy)
    app = make_app(tp_degree=8)
    report = app.audit(submodels=[TAG_TOKEN_GENERATION])
    findings = errors_of(report, "collectives")
    assert findings, report.to_json()
    msg = findings[0].message
    assert "token_generation_model[64]" == findings[0].program
    assert "exceed the policy budget" in msg


def test_dtype_drift_violation_detected():
    """An injected fp32 detour on a bf16 tensor (outside the norm/softmax/
    rope/logits islands) is flagged with its traceback location."""

    def drifting_forward(arch, inv_freq, params, cache, batch, **kw):
        out, cache = causal_lm_forward(arch, inv_freq, params, cache, batch, **kw)
        weight = next(
            leaf for leaf in jax.tree_util.tree_leaves(params)
            if leaf.dtype == jnp.bfloat16
        )
        leak = weight.astype(jnp.float32)  # seeded upcast
        out = dict(out)
        out["tokens"] = out["tokens"] + (leak.sum() * 0).astype(out["tokens"].dtype)
        return out, cache

    app = make_app()
    w = seeded_wrapper(app, drifting_forward)
    findings = errors_of(audit_seeded(app, w), "dtype_drift")
    assert findings, "seeded fp32 upcast not flagged"
    assert "drifting_forward" in findings[0].message or "upcast" in findings[0].message
    assert findings[0].program == "seeded_model[64]"


def test_dtype_drift_clean_on_reference_programs():
    """The shipped bf16 programs keep fp32 only in allowlisted islands."""
    report = make_app().audit(checkers=["dtype_drift"])
    assert errors_of(report, "dtype_drift") == []


def test_baked_constant_violation_detected():
    """A weight closed over instead of passed as an argument becomes a jaxpr
    constant above the size threshold."""
    BIG = np.ones((512, 512), dtype=np.float32)  # 1 MiB

    def baking_forward(arch, inv_freq, params, cache, batch, **kw):
        out, cache = causal_lm_forward(arch, inv_freq, params, cache, batch, **kw)
        baked = jnp.asarray(BIG)  # closed-over weight -> baked constant
        out = dict(out)
        out["tokens"] = out["tokens"] + (baked.sum() * 0).astype(out["tokens"].dtype)
        return out, cache

    app = make_app()
    w = seeded_wrapper(app, baking_forward)
    findings = errors_of(audit_seeded(app, w), "baked_constants")
    assert findings, "seeded 1 MiB constant not flagged"
    assert "[512, 512]" in findings[0].message
    assert findings[0].program == "seeded_model[64]"
    # and the reference programs carry nothing near the threshold
    clean = make_app().audit(checkers=["baked_constants"])
    assert errors_of(clean, "baked_constants") == []


def test_required_strategy_finding_via_auditor(monkeypatch):
    monkeypatch.setattr(
        ModelWrapper,
        "_required_strategies",
        lambda self: (("fake_kernel_flag", ("strategy_that_never_engages",)),),
    )
    app = make_app()
    report = app.audit(submodels=[TAG_TOKEN_GENERATION])
    findings = errors_of(report, "required_strategies")
    assert findings
    assert "fake_kernel_flag" in findings[0].message
    assert "token_generation_model[64]" in findings[0].message


# ---------------------------------------------------------------------------
# MoE TPxEP collective budget (the ROADMAP invariant: dispatch/combine
# counts derived from moe_*_degree instead of the generous flat allowance)
# ---------------------------------------------------------------------------

def make_moe_app(**tpu_kwargs):
    """Tiny mixtral on the 8-device CPU mesh with an explicit TPxEP regime
    (moe_ep_degree=2 carves the ep axis out of tp=8)."""
    from nxdi_tpu.config import TpuConfig
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    family, cfg_cls = get_family("mixtral")
    defaults = dict(
        tp_degree=8,
        seq_len=64,
        max_context_length=32,
        dtype="float32",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True,
        moe_ep_degree=2,
    )
    defaults.update(tpu_kwargs)
    cfg = cfg_cls(
        TpuConfig(**defaults),
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=8, num_key_value_heads=8, vocab_size=256,
        rms_norm_eps=1e-5, num_local_experts=8, num_experts_per_tok=2,
    )

    class App(TpuModelForCausalLM):
        pass

    return App("<abstract>", cfg, model_family=family)


def test_moe_tpxep_budget_clean_and_exact():
    """The shipped sparse TPxEP program fits the budget DERIVED from
    moe_ep_degree — and that budget allows ZERO all-to-all/extra
    all-gathers (the old flat allowance granted 4 of each)."""
    app = make_moe_app()
    report = app.audit(submodels=[TAG_TOKEN_GENERATION])
    assert errors_of(report, "collectives") == [], report.to_json()
    (prog,) = report.programs
    assert prog.budget["all-to-all"] == 0
    assert prog.collectives["all-to-all"] == 0
    # the combine really is the single derived psum allowance
    assert prog.budget["all-reduce"] <= 5


def test_moe_tpxep_budget_violation_detected(monkeypatch):
    """Seeded violation: extra per-layer psums smuggled into the MoE combine
    (a wasteful regime regression). The OLD flat budget (+2 MoE all-reduce)
    would have absorbed them; the moe_ep_degree-derived budget (+1) trips
    with the regime named in the explain."""
    import nxdi_tpu.ops.moe as ops_moe
    from nxdi_tpu.parallel.mesh import AXIS_MP

    orig = ops_moe._sparse_moe

    def wasteful(moe, experts, x, weights, idx, hidden_spec, layer=None):
        out = orig(moe, experts, x, weights, idx, hidden_spec, layer)
        mesh = jax.sharding.get_abstract_mesh()
        world = 1
        for a in AXIS_MP:
            world *= mesh.shape.get(a, 1)
        f = jax.shard_map(
            lambda v: jax.lax.psum(v, AXIS_MP), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec(),
            out_specs=jax.sharding.PartitionSpec(), check_vma=False,
        )
        for _ in range(3):  # 3 unbudgeted all-reduces per layer body
            out = f(out) / world
        return out

    monkeypatch.setattr(ops_moe, "_sparse_moe", wasteful)
    app = make_moe_app()
    report = app.audit(submodels=[TAG_TOKEN_GENERATION])
    findings = errors_of(report, "collectives")
    assert findings, report.to_json()
    msg = findings[0].message
    assert "all-reduce" in msg and "exceed the policy budget" in msg
    assert "moe_ep_degree=2" in msg  # the derived regime is in the explain


# ---------------------------------------------------------------------------
# KV-layout addressing (the ROADMAP unchecked-invariant, now checked)
# ---------------------------------------------------------------------------

def paged_app():
    return make_app(is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=24)


def test_kv_layout_clean_on_paged_and_contiguous_reference_apps():
    """Shipped programs: paged apps keep their addressing inputs live,
    contiguous apps carry none — both audit clean."""
    assert errors_of(paged_app().audit(checkers=["kv_layout"]), "kv_layout") == []
    assert errors_of(make_app().audit(checkers=["kv_layout"]), "kv_layout") == []


def test_kv_layout_dead_paged_inputs_detected():
    """A paged program whose forward ignores slot_mapping/block_table (the
    addressing inputs are pruned by kept_var_idx) compiles fine but routes
    every KV write nowhere — the checker must flag BOTH dead inputs."""

    def dead_layout_forward(arch, inv_freq, params, cache, batch, **kw):
        batch = dict(batch)
        # constants of the right shape: the real inputs become provably dead
        batch["slot_mapping"] = jnp.full(batch["slot_mapping"].shape, -1, jnp.int32)
        batch["block_table"] = jnp.full(batch["block_table"].shape, -1, jnp.int32)
        return causal_lm_forward(arch, inv_freq, params, cache, batch, **kw)

    app = paged_app()
    w = seeded_wrapper(app, dead_layout_forward)
    findings = errors_of(
        audit_seeded(app, w), "kv_layout"
    )
    assert len(findings) == 2, findings
    msg = " | ".join(f.message for f in findings)
    assert "slot_mapping" in msg and "block_table" in msg
    assert "DROPPED" in msg
    assert all(f.program == "seeded_model[64]" for f in findings)


def test_kv_layout_live_input_in_nonpaged_program_detected():
    """The vice-versa mixup: a NON-paged program that consumes a live
    block_table input is addressing a pool no host code maintains."""

    def mixup_forward(arch, inv_freq, params, cache, batch, **kw):
        out, cache = causal_lm_forward(arch, inv_freq, params, cache, batch, **kw)
        leak = batch["block_table"].sum()  # genuinely consumed -> stays live
        out = dict(out)
        out["tokens"] = out["tokens"] + (leak * 0).astype(out["tokens"].dtype)
        return out, cache

    app = make_app()  # contiguous layout
    w = seeded_wrapper(
        app, mixup_forward, extra_inputs={"block_table": ((8,), np.int32)}
    )
    findings = errors_of(audit_seeded(app, w), "kv_layout")
    assert findings, "live paged input in a non-paged program not flagged"
    assert "block_table" in findings[0].message
    assert "mixup" in findings[0].message


# ---------------------------------------------------------------------------
# mixed prefill+decode dispatch program
# ---------------------------------------------------------------------------

def mixed_app(**kw):
    defaults = dict(
        is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=24,
        ctx_batch_size=1, tkg_batch_size=2, mixed_dispatch=True,
    )
    defaults.update(kw)
    return make_app(**defaults)


def test_mixed_program_clean_on_mixed_reference_app():
    """The shipped mixed programs keep all three ragged row-descriptor
    inputs live and donate the cache at every token-bucket rung — and the
    checker is inert on apps without a mixed submodel."""
    from nxdi_tpu.runtime.model_wrapper import TAG_MIXED

    report = mixed_app().audit(submodels=[TAG_MIXED])
    assert errors_of(report, "mixed_program") == [], report.to_json()
    assert errors_of(report, "donation") == [], report.to_json()
    assert report.programs, "mixed submodel compiled no programs"
    # one program per token-bucket rung of the packed ladder
    assert all(p.tag == TAG_MIXED for p in report.programs)
    # non-mixed apps: zero mixed_program findings anywhere
    clean = paged_app().audit(checkers=["mixed_program"])
    assert [f for f in clean.findings if f.checker == "mixed_program"] == []


def test_mixed_program_dead_row_ids_detected():
    """Seeded violation: a mixed-tagged program whose forward ignores
    ``mixed_row_ids`` (constant-folded to -1, so kept_var_idx prunes the
    input) would attend packed tokens across requests — flagged with the
    input named."""
    from nxdi_tpu.runtime.model_wrapper import TAG_MIXED

    def dead_rows_forward(arch, inv_freq, params, cache, batch, **kw):
        batch = dict(batch)
        batch["mixed_row_ids"] = jnp.full(
            batch["mixed_row_ids"].shape, -1, jnp.int32
        )
        return causal_lm_forward(arch, inv_freq, params, cache, batch, **kw)

    app = paged_app()
    w = seeded_wrapper(
        app, dead_rows_forward, tag=TAG_MIXED,
        extra_inputs={"mixed_row_ids": ((-1,), np.int32)},
    )
    findings = errors_of(audit_seeded(app, w), "mixed_program")
    assert findings, "seeded dead mixed_row_ids not flagged"
    msg = " | ".join(f.message for f in findings)
    assert "mixed_row_ids" in msg and "DROPPED" in msg


# ---------------------------------------------------------------------------
# device-resident decode loop
# ---------------------------------------------------------------------------

def test_device_loop_clean_on_device_loop_reference_app():
    """The shipped ``tkg_device_loop`` programs lower an actual
    ``stablehlo.while``, keep both per-row halt vectors live, and donate
    the cache at every cap rung — and the checker is inert on apps without
    a device-loop submodel."""
    from nxdi_tpu.runtime.model_wrapper import TAG_DEVICE_LOOP

    report = make_app(device_loop=True).audit(submodels=[TAG_DEVICE_LOOP])
    assert errors_of(report, "device_loop") == [], report.to_json()
    assert errors_of(report, "donation") == [], report.to_json()
    assert report.programs, "device-loop submodel compiled no programs"
    assert all(p.tag == TAG_DEVICE_LOOP for p in report.programs)
    # non-loop apps: zero device_loop findings anywhere
    clean = make_app().audit(checkers=["device_loop"])
    assert [f for f in clean.findings if f.checker == "device_loop"] == []


def test_device_loop_dead_halt_vectors_detected():
    """Seeded violation: a loop-tagged program whose forward ignores
    ``budget_steps`` and ``eos_token_ids`` (constant-folded, so
    kept_var_idx prunes the inputs) would run every lane to the cap —
    flagged with each pruned halt vector named."""
    from nxdi_tpu.runtime.model_wrapper import (
        MULTISTEP_EOS_SLOTS,
        TAG_DEVICE_LOOP,
    )

    def dead_halt_forward(arch, inv_freq, params, cache, batch, **kw):
        batch = dict(batch)
        batch["budget_steps"] = jnp.full(
            batch["budget_steps"].shape, 0, jnp.int32
        )
        batch["eos_token_ids"] = jnp.full(
            batch["eos_token_ids"].shape, -1, jnp.int32
        )
        return causal_lm_forward(arch, inv_freq, params, cache, batch, **kw)

    app = make_app()
    w = seeded_wrapper(
        app, dead_halt_forward, tag=TAG_DEVICE_LOOP,
        extra_inputs={
            "budget_steps": ((), np.int32),
            "eos_token_ids": ((MULTISTEP_EOS_SLOTS,), np.int32),
        },
    )
    findings = errors_of(audit_seeded(app, w), "device_loop")
    assert findings, "seeded dead halt vectors not flagged"
    msg = " | ".join(f.message for f in findings)
    assert "budget_steps" in msg and "eos_token_ids" in msg
    assert "DROPPED" in msg


def test_device_loop_missing_while_detected():
    """Seeded violation: a loop-tagged program whose traced jaxpr has no
    ``while`` primitive (a single fixed step consuming the halt vectors)
    reverted to fixed-rung semantics — flagged, and the live halt vectors
    raise no liveness findings of their own. The layer scan's own
    ``stablehlo.while`` must NOT mask this."""
    from nxdi_tpu.runtime.model_wrapper import (
        MULTISTEP_EOS_SLOTS,
        TAG_DEVICE_LOOP,
    )

    def no_loop_forward(arch, inv_freq, params, cache, batch, **kw):
        batch = dict(batch)
        budget = batch.pop("budget_steps")
        eos = batch.pop("eos_token_ids")
        out, cache = causal_lm_forward(arch, inv_freq, params, cache, batch, **kw)
        out = dict(out)
        # halt vectors stay LIVE (data dependence) but loop-free
        keep = (budget.sum() + eos.sum()) * 0
        out["tokens"] = out["tokens"] + keep.astype(out["tokens"].dtype)
        return out, cache

    app = make_app()
    w = seeded_wrapper(
        app, no_loop_forward, tag=TAG_DEVICE_LOOP,
        extra_inputs={
            "budget_steps": ((), np.int32),
            "eos_token_ids": ((MULTISTEP_EOS_SLOTS,), np.int32),
        },
    )
    findings = errors_of(audit_seeded(app, w), "device_loop")
    assert findings, "seeded loop-free device-loop program not flagged"
    msg = " | ".join(f.message for f in findings)
    assert "traced away" in msg
    assert "DROPPED" not in msg


def test_device_loop_undonated_cache_detected(monkeypatch):
    """Seeded violation: device-loop programs compiled WITHOUT cache
    donation double the KV residency for the whole launch — flagged per
    cache leaf."""
    from nxdi_tpu.runtime.model_wrapper import TAG_DEVICE_LOOP

    orig_jit = jax.jit

    def jit_without_donation(*args, **kwargs):
        kwargs.pop("donate_argnums", None)
        return orig_jit(*args, **kwargs)

    monkeypatch.setattr(jax, "jit", jit_without_donation)
    app = make_app(device_loop=True)
    report = app.audit(
        submodels=[TAG_DEVICE_LOOP], checkers=["device_loop"]
    )
    findings = errors_of(report, "device_loop")
    assert findings, "undonated device-loop cache not flagged"
    msg = " | ".join(f.message for f in findings)
    assert "'k'" in msg and "'v'" in msg and "donation" in msg


# ---------------------------------------------------------------------------
# LoRA adapter sharding
# ---------------------------------------------------------------------------

LORA_CFG = {"max_loras": 2, "max_lora_rank": 4}


def test_lora_sharding_clean_on_lora_app():
    """The shipped lora_spec_update keeps adapter buffers on the base
    projections' axes — a tp=8 LoRA app audits clean, and a non-LoRA app
    produces no lora_sharding findings at all."""
    app = make_app(tp_degree=8, lora_config=dict(LORA_CFG))
    assert errors_of(app.audit(checkers=["lora_sharding"]), "lora_sharding") == []
    assert errors_of(
        make_app(tp_degree=8).audit(checkers=["lora_sharding"]), "lora_sharding"
    ) == []


def test_lora_sharding_violation_detected(monkeypatch):
    """Seeded violation: a REPLICATED lora_B next to the column-parallel
    q_proj weight (the silent per-layer all-gather the ROADMAP invariant
    describes) must fail the audit with the module named."""
    import nxdi_tpu.lora as lora_pkg
    from nxdi_tpu.parallel.layers import REPLICATED

    orig = lora_pkg.lora_spec_update

    def bad(specs, lora_cfg):
        specs = orig(specs, lora_cfg)
        specs["layers"]["attn"]["q_proj"]["lora_B"] = REPLICATED
        return specs

    monkeypatch.setattr(lora_pkg, "lora_spec_update", bad)
    app = make_app(tp_degree=8, lora_config=dict(LORA_CFG))
    findings = errors_of(app.audit(checkers=["lora_sharding"]), "lora_sharding")
    assert findings, "replicated lora_B next to a tp-sharded weight not flagged"
    msg = findings[0].message
    assert "q_proj" in msg and "lora_B" in msg and "all-gathers" in msg
    # only the seeded module is named — the healthy targets stay clean
    assert all("q_proj" in f.message for f in findings)
    # the spec comparison is program-independent: ONE finding per audit,
    # not one per (submodel, bucket) program
    assert len(findings) == 1


# ---------------------------------------------------------------------------
# HBM fit (the cost observatory's budget, run as an auditor checker)
# ---------------------------------------------------------------------------

def test_hbm_fit_clean_on_reference_app():
    """The tiny reference app trivially fits a v5e; no hbm_fit findings."""
    report = make_app().audit(checkers=["hbm_fit"])
    assert errors_of(report, "hbm_fit") == []


def test_hbm_fit_overbudget_config_detected():
    """A declared chip the config cannot fit (weights + max-live KV + temp
    vs per-chip HBM) fails the audit with the GiB breakdown."""
    app = make_app(chip={"hbm_gib": 1e-6})  # a part with ~1 KiB of HBM
    report = app.audit(submodels=[TAG_TOKEN_GENERATION])
    findings = errors_of(report, "hbm_fit")
    assert findings, report.to_json()
    msg = findings[0].message
    assert "exceeds" in msg and "max-live KV" in msg and "GiB" in msg
    assert findings[0].program == "token_generation_model[64]"


def test_hbm_fit_sharding_raises_the_budget():
    """The budget derives from the sharding world like the collective
    budget: the same over-budget weights fit once divided over tp chips."""
    from nxdi_tpu.analysis.costs import hbm_residency, resolve_chip

    app = make_app()
    chip = resolve_chip(app.tpu_config)
    big_weights = int(chip.hbm_bytes * 1.5)
    assert not hbm_residency(big_weights, 0, 1, chip)["fits"]
    assert hbm_residency(big_weights, 0, 8, chip)["fits"]


# ---------------------------------------------------------------------------
# quantized-path dtype rules (the last ROADMAP invariant, now checked)
# ---------------------------------------------------------------------------

def test_quantized_dtype_clean_on_quantized_apps():
    """The shipped w8a8 paths audit clean under both activation-quant modes,
    and the checker is inert on unquantized / weight-only configs."""
    for mode in ("dynamic", "static"):
        report = make_app(
            quantized=True, activation_quantization_type=mode
        ).audit(checkers=["quantized_dtype"])
        assert errors_of(report, "quantized_dtype") == [], (mode, report.to_json())
    # unquantized: out of scope, zero findings
    assert make_app().audit(checkers=["quantized_dtype"]).findings == []
    # weight-only int8 (no activation quant): upcast-into-matmul is the
    # design there — the checker must not flag it
    report = make_app(quantized=True).audit(checkers=["quantized_dtype"])
    assert errors_of(report, "quantized_dtype") == []


def test_quantized_dtype_upcast_detour_detected(monkeypatch):
    """A dequantize-before-dot regression (the weight-only fallback engaged
    while the config declares the int8 MXU path) is flagged: no dot reaches
    int8 x int8 operands un-upcast."""
    import nxdi_tpu.ops.quantization as quant_ops

    orig = quant_ops.quantized_linear

    def upcast_linear(x, p, act_quant=None, clamp_bound=None):
        return orig(x, p, act_quant=None, clamp_bound=None)  # fp32 detour

    monkeypatch.setattr(quant_ops, "quantized_linear", upcast_linear)
    report = make_app(
        quantized=True, activation_quantization_type="dynamic"
    ).audit(checkers=["quantized_dtype"], submodels=[TAG_TOKEN_GENERATION])
    findings = errors_of(report, "quantized_dtype")
    assert findings, report.to_json()
    msg = findings[0].message
    assert "NO dot_general contracts int8" in msg and "detour" in msg
    assert findings[0].program == "token_generation_model[64]"


def test_quantized_dtype_static_scale_recompute_detected(monkeypatch):
    """Under static activation quantization the calibrated input_scale must
    be consumed as a constant: a hot path that recomputes the per-token
    amax (the dynamic branch engaged under a static declaration) is
    flagged."""
    import nxdi_tpu.ops.quantization as quant_ops

    orig = quant_ops.quantized_linear

    def recomputing_linear(x, p, act_quant=None, clamp_bound=None):
        return orig(x, p, act_quant="dynamic", clamp_bound=clamp_bound)

    monkeypatch.setattr(quant_ops, "quantized_linear", recomputing_linear)
    report = make_app(
        quantized=True, activation_quantization_type="static"
    ).audit(checkers=["quantized_dtype"], submodels=[TAG_TOKEN_GENERATION])
    findings = errors_of(report, "quantized_dtype")
    assert findings, report.to_json()
    assert "RECOMPUTED" in findings[0].message
    assert "input_scale" in findings[0].message


# ---------------------------------------------------------------------------
# cross-program cache-format agreement (the ROADMAP invariant, now checked)
# ---------------------------------------------------------------------------

def test_cache_format_agreement_clean_on_reference_app():
    """Prefill and decode resolve their AUTO cache layouts identically, and
    the auditor recorded the per-leaf formats it compared."""
    report = make_app().audit()
    assert errors_of(report, "cache_format") == []
    formats = [p.cache_formats for p in report.programs]
    assert all(f is not None and len(f) == 2 for f in formats)  # k and v
    assert len({f for fs in formats for f in fs}) == 1  # one layout overall


def test_cache_format_disagreement_detected(monkeypatch):
    """A prefill/decode pair resolving DIFFERENT cache layouts is flagged:
    every phase transition would pay a full-cache relayout."""
    from nxdi_tpu.analysis import auditor as auditor_mod

    calls = {"n": 0}

    def drifting_formats(compiled):
        # each compiled program reports a different per-leaf layout
        calls["n"] += 1
        return {"k": f"fmt{calls['n']}", "v": f"fmt{calls['n']}"}

    monkeypatch.setattr(auditor_mod, "_cache_input_formats", drifting_formats)
    report = make_app().audit()
    findings = errors_of(report, "cache_format")
    assert findings, report.to_json()
    msg = findings[0].message
    assert "relayout" in msg and "disagree" in msg
    # names both sides of the disagreeing pair
    assert "context_encoding_model[32]" in msg
    assert "token_generation_model[64]" in msg


def test_unknown_checker_name_still_surfaces():
    """`checkers=["kv_layuot"]` (a typo) must not read as "ran clean": every
    program reports the unknown name; the valid cross-program "cache_format"
    selection stays silent."""
    report = make_app().audit(checkers=["donation", "kv_layuot"])
    msgs = [f.message for f in report.findings if f.checker == "auditor"]
    assert msgs and all("kv_layuot" in m for m in msgs)
    clean = make_app().audit(checkers=["cache_format"])
    assert [f for f in clean.findings if f.checker == "auditor"] == []


def test_cache_format_agreement_pure_function():
    """Both directions through the comparison itself (no compile needed)."""
    from nxdi_tpu.analysis import check_cache_format_agreement
    from nxdi_tpu.analysis.auditor import ProgramReport

    agree = [
        ProgramReport("cte", 32, "cte[32]", cache_formats=("A", "A")),
        ProgramReport("tkg", 64, "tkg[64]", cache_formats=("A", "A")),
        ProgramReport("x", None, "x[?]", cache_formats=None),  # no view: skipped
    ]
    assert check_cache_format_agreement(agree) == []
    disagree = [
        ProgramReport("cte", 32, "cte[32]", cache_formats=("A", "A")),
        ProgramReport("tkg", 64, "tkg[64]", cache_formats=("A", "B")),
    ]
    findings = check_cache_format_agreement(disagree)
    assert len(findings) == 1
    assert findings[0].checker == "cache_format"
    assert findings[0].program == "tkg[64]"
    # the finding landed on the report too (audit_application's view)
    assert disagree[1].findings == findings


# ---------------------------------------------------------------------------
# retrace guard
# ---------------------------------------------------------------------------

def loaded_app(**tpu_kwargs):
    """A loaded (random-weight) app: warmup compiles every program, sealing
    the retrace guard."""
    app = make_app(skip_warmup=False, **tpu_kwargs)

    class App(type(app)):
        pass

    struct = params_shape_struct(ml, app.config, ml.build_arch(app.config))
    rng = np.random.default_rng(0)
    import ml_dtypes

    weights = jax.tree_util.tree_map(
        lambda s: (rng.standard_normal(s.shape) * 0.02).astype(
            ml_dtypes.bfloat16 if s.dtype == jnp.bfloat16 else s.dtype
        ),
        struct,
    )
    app.build_params = lambda: weights
    app.load()
    return app


def test_retrace_guard_raises_after_serving():
    from nxdi_tpu.analysis import RetraceAfterServingError

    app = loaded_app(retrace_guard="error")
    assert app.retrace_guard.sealed
    assert app.retrace_guard.lowerings  # warmup recorded every program
    w = app.models[TAG_TOKEN_GENERATION]
    # a stray retrace mid-serving: the compiled program evaporated (new
    # bucket, signature drift, eviction) and the next request must re-lower
    w._programs[64]._compiled = None
    with pytest.raises(RetraceAfterServingError, match=r"token_generation_model\[64\]"):
        app.forward(
            np.array([[7]], dtype=np.int32),
            np.array([[3]], dtype=np.int32),
        )


def test_retrace_guard_warn_mode_records(caplog):
    import logging

    app = loaded_app(retrace_guard="warn")
    w = app.models[TAG_TOKEN_GENERATION]
    w._programs[64]._compiled = None
    with caplog.at_level(logging.WARNING, logger="nxdi_tpu"):
        app.forward(
            np.array([[7]], dtype=np.int32),
            np.array([[3]], dtype=np.int32),
        )
    assert any("lowered AFTER serving started" in r.message for r in caplog.records)
    assert app.retrace_guard.violations
    # the violation also surfaces in the audit report
    report = app.audit()
    assert any(f.checker == "retrace" for f in report.findings)


def test_collective_summary_from_loaded_app():
    """The probes' summary: per-program collective counts straight from the
    executables a loaded app holds (no retracing/compiling)."""
    from nxdi_tpu.analysis import collective_summary

    app = loaded_app()
    summary = collective_summary(app)
    assert set(summary) == {
        "context_encoding_model[32]", "token_generation_model[64]",
    }
    for counts in summary.values():  # tp=1: no collectives at all
        assert counts == {}


def test_retrace_guard_not_sealed_with_skip_warmup():
    app = make_app(skip_warmup=True)
    app._build_wrappers()
    assert not app.retrace_guard.sealed


# ---------------------------------------------------------------------------
# satellite: required-strategy verification provably runs on the AOT path
# ---------------------------------------------------------------------------

def test_required_strategy_check_runs_on_aot_compile_path(monkeypatch, tmp_path):
    """Regression: `app.compile()` (the AOT artifact path through
    `_AutoLayoutProgram.compile`) must enforce required kernel strategies just
    like the lazy first-call path — a flag that cannot engage raises at
    compile time, naming the submodel and bucket."""
    monkeypatch.setattr(
        ModelWrapper,
        "_required_strategies",
        lambda self: (("fake_kernel_flag", ("strategy_that_never_engages",)),),
    )
    # the check under test raises while lowering; keep this worker's later
    # tests out of the persistent compile cache
    monkeypatch.setattr(
        "nxdi_tpu.runtime.application.enable_persistent_cache", lambda: None
    )
    app = make_app()
    with pytest.raises(RuntimeError, match=r"fake_kernel_flag") as ei:
        app.compile(str(tmp_path / "artifact"))
    assert "[" in str(ei.value)  # names the submodel[bucket] program


def test_required_strategy_check_runs_on_first_call_path(monkeypatch):
    monkeypatch.setattr(
        ModelWrapper,
        "_required_strategies",
        lambda self: (("fake_kernel_flag", ("strategy_that_never_engages",)),),
    )
    with pytest.raises(RuntimeError, match=r"fake_kernel_flag"):
        loaded_app()


# ---------------------------------------------------------------------------
# serving-role program-set audit (ISSUE 15 satellite): role-restricted apps
# ship no dead submodels; one seeded violation per direction
# ---------------------------------------------------------------------------

def _role_app(role):
    return make_app(
        is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=24, role=role
    )


def test_program_set_clean_on_both_role_reference_apps():
    """The role reference apps the disaggregation tier deploys audit clean:
    config-level gating (config.py + application.py) and the compiled
    reality agree on what each role ships."""
    for role in ("prefill", "decode"):
        report = _role_app(role).audit(checkers=["program_set"])
        assert errors_of(report, "program_set") == [], role
    # the unified app never triggers the checker at all
    assert errors_of(make_app().audit(checkers=["program_set"]),
                     "program_set") == []


def test_program_set_decode_role_with_cte_detected():
    """Seeded violation, decode direction: a unified build (CTE ladder
    compiled) re-labeled role='decode' post-build — the checker flags every
    context-encoding program as dead weight."""
    app = make_app(is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=24)
    app._build_wrappers()  # compile the unified program set first
    app.tpu_config.role = "decode"  # bypass build-time gating on purpose
    findings = errors_of(app.audit(checkers=["program_set"]), "program_set")
    assert findings, "dead CTE programs must be flagged on a decode-role app"
    assert all(f.submodel == TAG_CONTEXT_ENCODING for f in findings)
    assert "dead weight" in findings[0].message


def test_program_set_prefill_role_with_multistep_detected():
    """Seeded violation, prefill direction: a multistep build
    (decode_steps_per_dispatch > 1 compiles tkg_multistep) re-labeled
    role='prefill' — the checker flags the multi-token decode programs a
    one-token-then-handoff engine can never dispatch."""
    app = make_app(decode_steps_per_dispatch=2)
    app._build_wrappers()
    app.tpu_config.role = "prefill"
    findings = errors_of(app.audit(checkers=["program_set"]), "program_set")
    assert findings, "multistep programs must be flagged on a prefill-role app"
    assert {f.submodel for f in findings} == {"tkg_multistep"}
