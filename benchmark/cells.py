"""BENCHMARK.json -> one cell and the files it names. No JAX in here.

A cell is one entry of ``workloads``: a configuration (``configs/<name>.json``,
the path is the manifest's ``file``) under a traffic mix
(``traffic/<name>.json``). Whatever belongs to one configuration, traffic mix,
generator, reference or metric is a file of its own, found by name — a later
PR adds files and manifest entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

#: directory of each kind of by-name python file, and the function it defines
PLUGIN_DIRS = {
    "generator": ("generators", "generate"),
    "reference": ("references", "forward"),
    "cost_model": ("cost_models", "tkg_step"),
    "per_layer": ("layer_metrics", "read"),
    "end_to_end": ("end_to_end", "read"),
}


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict  # the configuration file, whole
    traffic_name: str
    traffic: dict  # the traffic file, whole
    chips: int
    end_to_end: List[dict]  # manifest entries of the metrics this cell reports
    per_layer: List[dict]


def load_manifest(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def read_json(rel_or_abs: str) -> dict:
    path = rel_or_abs if os.path.isabs(rel_or_abs) else os.path.join(ROOT, rel_or_abs)
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def plugin_path(kind: str, name: str) -> str:
    return os.path.join(BENCH_DIR, PLUGIN_DIRS[kind][0], f"{name}.py")


def load_plugin(kind: str, name: str, function: Optional[str] = None):
    """The function of the by-name file ``<kind dir>/<name>.py``. ``function``
    asks for a second one the file MAY define beside it (a reference's
    ``routing_margins``): None where it does not."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = plugin_path(kind, name)
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path
    )
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if function is not None:
        return getattr(module, function, None)
    return getattr(module, PLUGIN_DIRS[kind][1])


def metrics_of(manifest: dict, kind: str, cell_name: str) -> List[dict]:
    """Manifest entries of ``kind`` (``end_to_end``/``per_layer``) that the
    cell reports: all without a ``workloads`` key, else those listing it."""
    return [
        m for m in manifest[kind]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def resolve(manifest: dict, workload: str) -> Cell:
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in manifest['workloads']]}"
        )
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    return Cell(
        name=entry["name"],
        config_name=entry["config"],
        config=read_json(cfg_entry["file"]),
        traffic_name=entry["traffic"],
        traffic=read_json(traffic_path(entry["traffic"])),
        chips=int(entry["chips"]),
        end_to_end=metrics_of(manifest, "end_to_end", entry["name"]),
        per_layer=metrics_of(manifest, "per_layer", entry["name"]),
    )


def check_manifest(manifest: dict) -> List[str]:
    """Every fault a later PR's added entries or files could have, as text;
    empty when the manifest and its files agree. The loader test runs this,
    so new cells are checked without editing a test."""
    bad: List[str] = []

    def name_ok(what: str, value: Optional[str]):
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{what}: name {value!r} uses characters outside [A-Za-z0-9_.-]")

    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for c in manifest["configs"]:
        name_ok("config", c["name"])
        for key in c["reduced"]:
            name_ok(f"config {c['name']} reduced", key)
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in manifest["paths"]):
            bad.append(f"config {c['name']}: file {c['file']} lies outside paths")
        try:
            body = read_json(c["file"])
        except (OSError, ValueError) as e:
            bad.append(f"config {c['name']}: {e}")
            continue
        for kind in ("reference", "cost_model"):
            named = body.get("benchmark", {}).get(kind)
            if not named or not os.path.isfile(plugin_path(kind, named)):
                bad.append(f"config {c['name']}: no {kind} file for {named!r}")
        if body.get("source") != c["source"]:
            bad.append(f"config {c['name']}: source differs between manifest and file")
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            name_ok(kind, m["name"])
            if not UNIT_RE.match(m.get("unit", "")):
                bad.append(f"{kind} {m['name']}: unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                bad.append(f"{kind} {m['name']}: better {m.get('better')!r}")
            if m["name"] != "setup_s" and not os.path.isfile(plugin_path(kind, m["name"])):
                bad.append(f"{kind} {m['name']}: no reader file")
    cells = set()
    for w in manifest["workloads"]:
        name_ok("workload", w["name"])
        name_ok(f"workload {w['name']} traffic", w["traffic"])
        if w["config"] not in configs:
            bad.append(f"workload {w['name']}: unknown config {w['config']!r}")
            continue
        if (w["config"], w["traffic"]) in cells:
            bad.append(f"workload {w['name']}: pair appears twice")
        cells.add((w["config"], w["traffic"]))
        try:
            traffic = read_json(traffic_path(w["traffic"]))
        except (OSError, ValueError) as e:
            bad.append(f"workload {w['name']}: {e}")
            continue
        gen = traffic.get("generator")
        if not gen or not os.path.isfile(plugin_path("generator", gen)):
            bad.append(f"traffic {w['traffic']}: no generator file for {gen!r}")
        try:
            cfg_chips = read_json(configs[w["config"]]["file"])["benchmark"]["chips"]
            if cfg_chips != w["chips"]:
                bad.append(f"workload {w['name']}: chips {w['chips']} but the "
                           f"configuration is laid out for {cfg_chips}")
        except (OSError, ValueError, KeyError) as e:
            bad.append(f"workload {w['name']}: {e!r}")
        reported = {m["name"] for m in metrics_of(manifest, "end_to_end", w["name"])}
        if "setup_s" not in reported or len(reported) < 2:
            bad.append(f"workload {w['name']}: needs setup_s and one more end-to-end metric")
        layer = metrics_of(manifest, "per_layer", w["name"])
        if not layer:
            bad.append(f"workload {w['name']}: no per-layer metric")
        for m in layer:
            if m["moves"] not in reported:
                bad.append(f"per_layer {m['name']} moves {m['moves']!r}, which "
                           f"workload {w['name']} does not report")
    names = [w["name"] for w in manifest["workloads"]]
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            for w in m.get("workloads", ()):
                if w not in names:
                    bad.append(f"{kind} {m['name']}: lists unknown workload {w!r}")
    return bad
