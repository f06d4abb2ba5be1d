"""BENCHMARK.json and the files it names agree. Written over the manifest, not
over today's cells: a later PR's added entries and files are checked by the
same tests without editing them."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402

MANIFEST = cells.load_manifest()
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = [(k, m["name"]) for k in ("end_to_end", "per_layer") for m in MANIFEST[k]]


def test_manifest_and_files_agree():
    assert cells.check_manifest(MANIFEST) == []


def test_manifest_has_exactly_the_contract_keys():
    assert sorted(MANIFEST) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"]
    )
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace"), m


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves_every_file_by_name(workload):
    cell = cells.resolve(MANIFEST, workload)
    assert cell.config["name"] == cell.config_name
    assert cell.config["reduced"] == next(
        c["reduced"] for c in MANIFEST["configs"] if c["name"] == cell.config_name
    )
    assert callable(cells.load_plugin("generator", cell.traffic["generator"]))
    assert callable(cells.load_plugin("reference", cell.config["benchmark"]["reference"]))
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported, (m["name"], m["moves"])
    # the cell's traffic fits the configuration's serving window and pool
    from benchmark import traffic_gen

    b = cell.config["benchmark"]
    longest = traffic_gen.max_prompt_len(cell.traffic) + cell.traffic["output_len"]["hi"]
    assert longest < b["seq_len"]
    assert -(-longest // b["pa_block_size"]) <= b["pa_num_blocks"]


@pytest.mark.parametrize("kind,name", METRICS)
def test_metric_has_a_reader_and_clean_names(kind, name):
    assert cells.NAME_RE.match(name)
    entry = next(m for m in MANIFEST[kind] if m["name"] == name)
    assert cells.UNIT_RE.match(entry["unit"])
    if name != "setup_s":
        assert callable(cells.load_plugin(kind, name))
    if kind == "per_layer":
        assert set(entry) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_every_layer_metric_file_is_in_the_manifest_or_documented():
    """A reader file nobody lists is dead weight (or a forgotten entry)."""
    listed = {m["name"] for m in MANIFEST["per_layer"]}
    on_disk = {
        f[:-3] for f in os.listdir(os.path.join(cells.BENCH_DIR, "layer_metrics"))
        if f.endswith(".py")
    }
    unproven = set(json.load(open(os.path.join(cells.BENCH_DIR, "unproven.json")))["per_layer"])
    assert on_disk - listed <= unproven
    assert listed <= on_disk


def test_published_sizes_are_uncut():
    for c in MANIFEST["configs"]:
        body = cells.read_json(c["file"])
        assert c["reduced"] == [] and body["reduced"] == []
        assert body["source"].startswith("https://huggingface.co/")
