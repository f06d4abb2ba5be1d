"""Cost observatory end-to-end over the llama CPU-mesh reference app:
``python -m nxdi_tpu.cli.costs`` prints a nonzero-FLOP/HBM CostSheet row
for every compiled (submodel, bucket[, steps]) program and gates on HBM
fit; ``cost_sheets`` reads a LOADED app's executables without retracing;
``cost_summary`` is the probes' compact line."""

import json

import pytest

from nxdi_tpu.config import OnDeviceSamplingConfig
from nxdi_tpu.runtime.model_wrapper import (
    TAG_CONTEXT_ENCODING,
    TAG_TOKEN_GENERATION,
)


def make_app(**tpu_kwargs):
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


# ---------------------------------------------------------------------------
# the CLI (the acceptance surface)
# ---------------------------------------------------------------------------

def test_cli_costs_reference_app(tmp_path, capsys):
    """`python -m nxdi_tpu.cli.costs --reference-app`: exit 0, one row per
    compiled (submodel, bucket) with nonzero FLOPs and HBM bytes."""
    from nxdi_tpu.cli.costs import main

    out = tmp_path / "costs.json"
    rc = main(["--reference-app", "-q", "--format", "text",
               "--json", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    payload = json.loads(out.read_text())
    assert payload["ok"] is True
    assert payload["chip"]["name"] == "v5e"
    programs = {p["submodel"]: p for p in payload["programs"]}
    assert set(programs) == {TAG_CONTEXT_ENCODING, TAG_TOKEN_GENERATION}
    for p in payload["programs"]:
        assert p["flops"] > 0 and p["hbm_bytes"] > 0, p["program"]
        assert p["floor_s"] > 0
        assert p["bound"] in ("compute", "hbm")
        assert p["fit"]["fits"] is True
        assert p["program"] in text  # the table prints every row


def test_cli_costs_multistep_rungs(tmp_path):
    """Multi-step rungs are separate programs with per-rung sheets: the K=4
    ladder compiles [2, 4] rungs and each K multiplies the per-step cost."""
    from nxdi_tpu.cli.costs import main

    out = tmp_path / "costs.json"
    rc = main(["--reference-app", "-q", "--decode-steps-per-dispatch", "4",
               "--format", "text", "--json", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    rungs = {
        p["steps"]: p for p in payload["programs"]
        if p["submodel"] == "tkg_multistep"
    }
    assert set(rungs) == {2, 4}
    assert rungs[4]["flops"] == pytest.approx(2 * rungs[2]["flops"])


def test_cli_costs_overbudget_chip_gates(tmp_path, capsys):
    """The exit-code gate: a part the model cannot fit fails with rc 1 and
    the rows say OVER."""
    from nxdi_tpu.cli.costs import main

    rc = main(["--reference-app", "-q", "--format", "text",
               "--chip", '{"hbm_gib": 1e-5}'])
    assert rc == 1
    assert "OVER" in capsys.readouterr().out


def test_cli_costs_usage_error():
    from nxdi_tpu.cli.costs import main

    assert main([]) == 2
    # bad --chip values are usage errors caught BEFORE the app build
    assert main(["--reference-app", "--chip", "{not json"]) == 2
    assert main(["--reference-app", "--chip", "v7"]) == 2


def test_cli_lint_accepts_cache_format_checker_name():
    """`--checkers cache_format` selects ONLY the cross-program pass: no
    per-program checker crash findings, clean exit on the reference app."""
    from nxdi_tpu.cli.lint import main as lint_main

    assert lint_main(["--reference-app", "-q", "--fail-on", "warning",
                      "--checkers", "cache_format"]) == 0


# ---------------------------------------------------------------------------
# python API on a loaded app (zero retracing)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def loaded_app():
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from nxdi_tpu.models.llama import modeling_llama as ml
    from nxdi_tpu.runtime.application import params_shape_struct

    app = make_app(skip_warmup=False)
    struct = params_shape_struct(ml, app.config, ml.build_arch(app.config))
    rng = np.random.default_rng(0)
    weights = jax.tree_util.tree_map(
        lambda s: (rng.standard_normal(s.shape) * 0.02).astype(
            ml_dtypes.bfloat16 if s.dtype == jnp.bfloat16 else s.dtype
        ),
        struct,
    )
    app.build_params = lambda: weights
    app.load()
    return app


def test_cost_sheets_from_loaded_app_use_xla_source(loaded_app):
    from nxdi_tpu.analysis import cost_sheets

    guard_before = dict(loaded_app.retrace_guard.lowerings)
    sheets = {s.label: s for s in cost_sheets(loaded_app)}
    assert set(sheets) == {
        "context_encoding_model[32]", "token_generation_model[64]",
    }
    for s in sheets.values():
        # warmup compiled everything, so XLA's analyses ground every sheet
        assert s.source == "xla"
        assert s.xla_flops is not None and s.xla_flops > 0
        assert s.flops > 0 and s.hbm_bytes > 0
        assert s.fit["fits"]
        # on the CPU backend the tiny programs agree with the analytic model
        # well within the 2x mismatch threshold
        assert s.mismatch is None, s.mismatch
    # reading sheets never lowered anything (no retrace)
    assert dict(loaded_app.retrace_guard.lowerings) == guard_before


def test_cost_summary_compact_lines(loaded_app):
    from nxdi_tpu.analysis import cost_summary

    summary = cost_summary(loaded_app)
    for label, line in summary.items():
        assert line["gflops"] > 0 and line["hbm_mb"] > 0
        assert line["bound"] in ("compute", "hbm")
        assert line["chip"] == "v5e"
        assert line["source"] == "xla"


def test_attachment_holds_app_weakly():
    """The export hooks must not keep the app alive: bench.py relies on
    `del app` releasing device weights before the next variant builds.
    After collection the hooks become no-ops and exports still succeed."""
    import gc
    import weakref

    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from nxdi_tpu.models.llama import modeling_llama as ml
    from nxdi_tpu.runtime.application import params_shape_struct

    app = make_app(skip_warmup=False)
    struct = params_shape_struct(ml, app.config, ml.build_arch(app.config))
    rng = np.random.default_rng(0)
    weights = jax.tree_util.tree_map(
        lambda s: (rng.standard_normal(s.shape) * 0.02).astype(
            ml_dtypes.bfloat16 if s.dtype == jnp.bfloat16 else s.dtype
        ),
        struct,
    )
    app.build_params = lambda: weights
    app.load()
    tel = app.telemetry
    assert tel.snapshot()["_cost_sheets"]  # attached and live
    wr = weakref.ref(app)
    del app
    gc.collect()
    assert wr() is None, "cost-gauge hooks kept the app (and its HBM) alive"
    snap = tel.snapshot()  # hooks no-op quietly after collection
    assert snap["_cost_sheets"] == []


def test_bench_sheet_selection_contract(loaded_app):
    """bench.py indexes sheets by (tag, bucket) and calls the measured
    joins — the exact access pattern must keep working."""
    from nxdi_tpu.analysis import cost_sheets

    sheets = {(s.tag, s.bucket): s for s in cost_sheets(loaded_app)}
    tkg = sheets[(TAG_TOKEN_GENERATION, 64)]
    cte = sheets[(TAG_CONTEXT_ENCODING, 32)]
    measured_s = 5e-3
    assert 0 < tkg.mfu_pct(measured_s) < 100
    assert 0 < tkg.hbm_bw_pct(measured_s) < 100
    assert tkg.gap_ratio(measured_s) > 1
    assert cte.mfu_pct(measured_s) > 0


# ---------------------------------------------------------------------------
# nothing hides the device: the chip is the attached one, the compile cache
# is where the environment says
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "kind,declared,want",
    [
        ("TPU v5 lite", None, "v5e"),
        ("TPU v5e", None, "v5e"),
        ("TPU v6 lite", None, "v6e"),
        ("TPU v5 lite", "v5e", "v5e"),
        ("TPU v5 lite", {"hbm_gib": 8.0}, "custom"),  # overrides ride the attached part
        ("TPU v5 lite", "v5p", "contradicts the attached"),
        ("TPU v5 lite", {"base": "v4"}, "contradicts the attached"),
        ("TPU v9 mega", None, "no ChipSpec for device kind"),
        ("TPU v9 mega", "v5e", "no ChipSpec for device kind"),
    ],
)
def test_resolve_chip_on_a_tpu_backend(monkeypatch, kind, declared, want):
    """On a TPU backend the attached device answers through the one table:
    an unknown kind raises, a contradicting declaration raises."""
    import types

    import jax

    from nxdi_tpu.analysis.costs import CHIP_SPECS, resolve_chip

    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])
    if want in CHIP_SPECS or want == "custom":
        chip = resolve_chip(None, override=declared)
        assert chip.name == want
        assert kind in chip.device_kinds
        if want == "custom":
            assert chip.hbm_gib == 8.0
            assert chip.bf16_tflops == CHIP_SPECS["v5e"].bf16_tflops
    else:
        with pytest.raises(ValueError, match=want):
            resolve_chip(None, override=declared)


@pytest.mark.parametrize("env_dir", [None, "named-from-outside"])
def test_compile_cache_directory_rule(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR set -> that directory, and no code sets
    another; unset -> ONE fixed path inside the checkout."""
    import os

    import jax

    from nxdi_tpu.runtime import application

    updates, made = {}, []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(application.os, "makedirs", lambda p, **kw: made.append(p))
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(repo, ".jax_cache")
        assert application.enable_persistent_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        assert application.enable_persistent_cache() == want
        assert "jax_compilation_cache_dir" not in updates
    assert made == [want]
    # keys must not depend on who lowered the program (Mosaic kernel bodies
    # carry their locations; see test_chip_compile.py)
    assert updates["jax_include_full_tracebacks_in_locations"] is False
    # same answer the next time: no temp name, pid or time in the path
    assert application.enable_persistent_cache() == want


def test_one_site_sets_the_compile_cache_directory():
    import os
    import re

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    sites = []
    for root in ("nxdi_tpu", "scripts", "bench.py", "chip_smoke.py"):
        top = os.path.join(repo, root)
        files = [top] if os.path.isfile(top) else [
            os.path.join(d, f) for d, _, fs in os.walk(top) for f in fs if f.endswith(".py")
        ]
        for path in files:
            with open(path) as f:
                for n, line in enumerate(f, 1):
                    if re.search(r"jax_compilation_cache_dir", line):
                        sites.append(f"{os.path.relpath(path, repo)}:{n}")
    assert len(sites) == 1 and sites[0].startswith("nxdi_tpu/runtime/application.py:"), sites
