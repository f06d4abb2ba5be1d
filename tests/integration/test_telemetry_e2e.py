"""Serving-telemetry end-to-end: a real generate call populates the
always-on registry (dispatch histograms, padding waste, TTFT/TPOT, spans),
the metrics CLI emits valid Prometheus text + JSON + a loadable Perfetto
trace, the /metrics endpoint serves scrapes, and instrumented dispatch stays
within a small overhead budget vs. telemetry disabled."""

import json
import re
import urllib.request

import numpy as np
import pytest

from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
from nxdi_tpu.generation.hf_adapter import HuggingFaceGenerationAdapter
from nxdi_tpu.models.llama import modeling_llama as llama
from nxdi_tpu.runtime.application import TpuModelForCausalLM

from spec_test_utils import make_tiny_hf_llama

PROMPT = np.array([[5, 9, 3, 17, 2, 8, 11, 42]], dtype=np.int64)


def _build_app(hf_model, hf_cfg, **extra):
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    tcfg = TpuConfig(
        tp_degree=1, seq_len=64, max_context_length=32, batch_size=1,
        dtype="float32", on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True, **extra,
    )
    cfg = llama.LlamaInferenceConfig(tcfg, load_config=lambda: hf_cfg.to_dict())

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", cfg, model_family=llama)
    app.load()
    return app


@pytest.fixture(scope="module")
def loaded_app():
    hf, cfg = make_tiny_hf_llama(seed=0)
    return _build_app(hf, cfg)


# ---------------------------------------------------------------------------
# generate() populates the registry
# ---------------------------------------------------------------------------

def test_generate_populates_registry_and_spans(loaded_app):
    app = loaded_app
    app.telemetry.reset()
    adapter = HuggingFaceGenerationAdapter(app)
    adapter.generate(PROMPT, max_new_tokens=4)

    tel = app.telemetry
    # dispatch counters per (submodel, bucket): 1 CTE + 3 TKG
    assert tel.dispatches_total.value(
        submodel="context_encoding_model", bucket="32", steps="1"
    ) == 1
    assert tel.dispatches_total.value(
        submodel="token_generation_model", bucket="64", steps="1"
    ) == 3
    # latency histograms carry every dispatch
    assert tel.dispatch_seconds.snapshot_series(
        submodel="token_generation_model", bucket="64", steps="1"
    ).count == 3
    # padding waste: 8 real of 32 padded CTE tokens = 0.75
    cte_waste = tel.padding_waste.snapshot_series(submodel="context_encoding_model")
    assert cte_waste.count == 1
    np.testing.assert_allclose(cte_waste.sum, 0.75)
    assert tel.real_tokens_total.value(submodel="context_encoding_model") == 8
    assert tel.padded_tokens_total.value(submodel="context_encoding_model") == 32
    # request metrics: one span, TTFT once, TPOT for the 3 decode tokens
    assert tel.requests_total.value() == 1
    assert tel.tokens_in_total.value() == 8
    assert tel.tokens_out_total.value() == 4
    assert tel.ttft_seconds.snapshot_series().count == 1
    assert tel.ttft_seconds.percentile(50) > 0
    assert tel.tpot_seconds.snapshot_series().count == 3
    (span,) = tel.spans.to_list()
    assert [p["name"] for p in span["phases"]] == ["pad", "prefill", "decode"]
    assert span["tokens_in"] == 8 and span["tokens_out"] == 4
    # lowerings were all pre-seal (skip_warmup app: nothing sealed, but the
    # phase label must say warmup, not serving)
    snap = tel.snapshot()
    phases = {
        s["labels"]["phase"] for s in snap["nxdi_program_lowerings_total"]["series"]
    }
    assert phases == {"warmup"}


def test_telemetry_off_records_nothing(tmp_path):
    hf, cfg = make_tiny_hf_llama(seed=0)
    app = _build_app(hf, cfg, telemetry="off")
    adapter = HuggingFaceGenerationAdapter(app)
    adapter.generate(PROMPT, max_new_tokens=2)
    assert not app.telemetry.enabled
    snap = app.telemetry.snapshot()
    assert snap == {"_spans": []}


# ---------------------------------------------------------------------------
# the cost -> telemetry join (PR: cost observatory)
# ---------------------------------------------------------------------------

def test_cost_gauges_exact_join_with_injected_latency(loaded_app, monkeypatch):
    """Injected dispatch latencies against the app's CostSheet must yield
    EXACT roofline gauge values in both the JSON snapshot and the
    Prometheus text: the join divides the histogram's mean (sum/count —
    exact, unlike an interpolated percentile) through the sheet."""
    app = loaded_app
    tel = app.telemetry
    tel.reset()
    for _ in range(3):  # three known dispatches, 2 ms each
        tel.record_dispatch("token_generation_model", 64, 1, 0.002)

    # at detail="basic" a dispatch's latency is its enqueue: a share of the
    # chip's peak from it would be one no chip can give, so none is published
    assert not tel.sync_dispatch
    basic = tel.snapshot()
    for fam in ("nxdi_program_mfu_pct", "nxdi_program_hbm_bw_pct", "nxdi_roofline_gap_ratio"):
        assert not basic.get(fam, {}).get("series") and f"{fam}{{" not in tel.prometheus_text()
    assert basic["_cost_sheets"]  # the sheets themselves ride every snapshot

    monkeypatch.setattr(tel, "sync_dispatch", True)  # detail="full": the wait is in it
    snap = tel.snapshot()
    sheets = {s["program"]: s for s in snap["_cost_sheets"]}
    sheet = sheets["token_generation_model[64]"]
    assert sheet["flops"] > 0 and sheet["hbm_bytes"] > 0
    hist = snap["nxdi_dispatch_seconds"]["series"][0]
    mean_s = hist["sum"] / hist["count"]  # what the attachment divides by

    # parenthesized exactly like CostSheet.mfu_pct/hbm_bw_pct so the float
    # arithmetic (and therefore the equality below) is bit-exact
    expected_mfu = 100.0 * sheet["flops"] / (
        mean_s * (sheet["chip"]["bf16_tflops"] * 1e12)
    )
    expected_bw = 100.0 * sheet["hbm_bytes"] / (
        mean_s * (sheet["chip"]["hbm_gbs"] * 1e9)
    )
    expected_gap = mean_s / sheet["floor_s"]

    def gauge(name):
        (row,) = snap[name]["series"]
        assert row["labels"] == {
            "submodel": "token_generation_model", "bucket": "64", "steps": "1",
        }
        return row["value"]

    assert gauge("nxdi_program_mfu_pct") == expected_mfu
    assert gauge("nxdi_program_hbm_bw_pct") == expected_bw
    assert gauge("nxdi_roofline_gap_ratio") == expected_gap

    text = tel.prometheus_text()
    labels = '{submodel="token_generation_model",bucket="64",steps="1"}'
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("nxdi_program_mfu_pct{")
    )
    assert line == f"nxdi_program_mfu_pct{labels} {repr(float(expected_mfu))}"


def test_cost_sheets_ride_every_snapshot(loaded_app):
    """One file captures measured + theoretical: any snapshot (and thus
    --metrics-out dumps and /metrics.json) embeds the CostSheet table."""
    app = loaded_app
    snap = app.telemetry.snapshot()
    assert {s["program"] for s in snap["_cost_sheets"]} == {
        "context_encoding_model[32]", "token_generation_model[64]",
    }
    for s in snap["_cost_sheets"]:
        assert s["flops"] > 0 and s["hbm_bytes"] > 0
        assert s["bound"] in ("compute", "hbm")
        assert s["fit"]["fits"] is True
    json.dumps(snap)  # the whole enriched snapshot stays JSON-able


def test_cost_attachment_failure_never_breaks_export(loaded_app):
    """A failing snapshot extra / attachment is logged and skipped; the
    export itself must survive (the gauges degrade, serving does not)."""
    app = loaded_app
    tel = app.telemetry
    def boom():
        raise RuntimeError("cost model exploded")
    tel.attach(boom)
    tel.add_snapshot_extra("_boom", boom)
    try:
        snap = tel.snapshot()
        assert "_boom" not in snap
        assert tel.prometheus_text().endswith("\n")
    finally:
        tel._attachments.remove(boom)
        tel._snapshot_extras.pop("_boom")


# ---------------------------------------------------------------------------
# exposition surfaces
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"  # comments
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9eE+.\-]+)$"  # samples
)


def test_prometheus_text_is_valid_exposition(loaded_app):
    app = loaded_app
    app.telemetry.reset()
    HuggingFaceGenerationAdapter(app).generate(PROMPT, max_new_tokens=3)
    text = app.telemetry.prometheus_text()
    assert text.endswith("\n")
    for line in text.rstrip("\n").splitlines():
        assert _PROM_LINE.match(line), f"invalid exposition line: {line!r}"
    # histogram series are complete: every _bucket family ends with +Inf and
    # carries _sum/_count
    assert 'le="+Inf"' in text
    for fam in ("nxdi_dispatch_seconds", "nxdi_request_ttft_seconds"):
        assert f"{fam}_sum" in text and f"{fam}_count" in text


def test_metrics_http_endpoint(loaded_app):
    app = loaded_app
    app.telemetry.reset()
    HuggingFaceGenerationAdapter(app).generate(PROMPT, max_new_tokens=2)
    server = app.telemetry.serve(port=0)  # ephemeral port
    try:
        base = f"http://127.0.0.1:{server.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "nxdi_dispatches_total" in text
        snap = json.loads(urllib.request.urlopen(f"{base}/metrics.json").read())
        assert "nxdi_request_ttft_seconds" in snap
        trace = json.loads(urllib.request.urlopen(f"{base}/trace.json").read())
        assert trace["traceEvents"]
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# the metrics CLI (the acceptance surface)
# ---------------------------------------------------------------------------

def test_cli_metrics_end_to_end(tmp_path, capsys):
    """``python -m nxdi_tpu.cli.metrics`` on the tiny reference app: valid
    Prometheus text + JSON containing per-submodel dispatch histograms,
    padding waste, block-manager gauges, and request TTFT/TPOT after demo
    generate traffic; the Perfetto trace loads and is structurally sound."""
    from nxdi_tpu.cli.metrics import main

    json_path = tmp_path / "metrics.json"
    trace_path = tmp_path / "trace.json"
    rc = main([
        "-q",
        "--requests", "2",
        "--max-new-tokens", "4",
        "--json", str(json_path),
        "--perfetto", str(trace_path),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    prom_part = out.split("\n{", 1)[0]
    for line in prom_part.rstrip("\n").splitlines():
        assert _PROM_LINE.match(line), f"invalid exposition line: {line!r}"

    snap = json.loads(json_path.read_text())
    # per-submodel dispatch histograms
    disp = snap["nxdi_dispatch_seconds"]["series"]
    submodels = {s["labels"]["submodel"] for s in disp}
    assert {"context_encoding_model", "token_generation_model"} <= submodels
    assert all(s["count"] >= 1 for s in disp)
    # padding waste + block-manager gauges + request TTFT/TPOT
    assert snap["nxdi_padding_waste_ratio"]["series"]
    assert snap["nxdi_kv_blocks_used"]["series"][0]["value"] == 0  # all freed
    assert snap["nxdi_kv_blocks_free"]["series"][0]["value"] > 0
    # frees count PER BLOCK (2 requests x 2 blocks each at this geometry)
    assert snap["nxdi_kv_block_frees_total"]["series"][0]["value"] == 4
    assert snap["nxdi_request_ttft_seconds"]["series"][0]["count"] == 2
    assert snap["nxdi_request_tpot_seconds"]["series"][0]["count"] >= 2
    assert snap["nxdi_requests_total"]["series"][0]["value"] == 2
    assert len(snap["_spans"]) == 2
    # the cost observatory rides the same snapshot: sheet table + the
    # CostSheet-joined roofline gauges for every dispatched program
    sheet_tags = {s["submodel"] for s in snap["_cost_sheets"]}
    assert {"context_encoding_model", "token_generation_model"} <= sheet_tags
    assert all(s["flops"] > 0 and s["hbm_bytes"] > 0 for s in snap["_cost_sheets"])
    mfu_tags = {
        s["labels"]["submodel"] for s in snap["nxdi_program_mfu_pct"]["series"]
    }
    assert {"context_encoding_model", "token_generation_model"} <= mfu_tags
    for fam in ("nxdi_program_mfu_pct", "nxdi_program_hbm_bw_pct"):
        assert f"{fam}{{" in prom_part  # exported in the Prometheus text too
        assert all(s["value"] > 0 for s in snap[fam]["series"])

    # the Perfetto trace loads and is structurally sound
    trace = json.loads(trace_path.read_text())
    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    slices = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in slices} >= {"request", "pad", "prefill", "decode"}
    for e in slices:
        assert e["ts"] >= 0 and e["dur"] >= 0


# ---------------------------------------------------------------------------
# overhead smoke: instrumented dispatch vs telemetry disabled
# ---------------------------------------------------------------------------

def test_dispatch_overhead_budget(loaded_app):
    """Always-on telemetry must stay cheap: the per-dispatch host cost with
    the default (basic) detail must be within 2 ms of hooks-disabled
    dispatch (in practice it is microseconds; 2 ms absorbs CI noise)."""
    import time

    app = loaded_app
    tel = app.telemetry
    ids = np.array([[7]], dtype=np.int32)
    pos = np.array([[40]], dtype=np.int32)

    def median_dispatch_ms(n=60):
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            app.forward(ids, pos)
            times.append((time.perf_counter() - t0) * 1000.0)
        return float(np.median(times))

    median_dispatch_ms(20)  # warm both paths' caches
    was = tel.enabled
    try:
        tel.enabled = False
        off_ms = median_dispatch_ms()
        tel.enabled = True
        on_ms = median_dispatch_ms()
    finally:
        tel.enabled = was
    assert on_ms - off_ms < 2.0, (on_ms, off_ms)
    # and the record path itself is sub-50us on average
    t0 = time.perf_counter()
    for _ in range(2000):
        tel.record_dispatch("token_generation_model", 64, 1, 0.001,
                            real_tokens=1, padded_tokens=1)
    per_record_us = (time.perf_counter() - t0) / 2000 * 1e6
    assert per_record_us < 50, per_record_us
