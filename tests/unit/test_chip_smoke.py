"""chip_smoke.py's contract with the driver: the LAST line of its standard
output is one JSON object with exactly the keys ``ok`` and ``device``
(``device`` exactly ``platform``, ``kind``, ``count``), on the failure path
and the success path alike — parsed here from a real subprocess."""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_contract(line: str) -> dict:
    rec = json.loads(line)
    assert isinstance(rec, dict) and set(rec) == {"ok", "device"}
    assert set(rec["device"]) == {"platform", "kind", "count"}
    return rec


def test_without_a_tpu_the_run_fails_fast_with_the_contract_line(tmp_path):
    """No accelerator: non-zero exit, ``"ok": false`` in a line of the same
    shape, nothing after it, and no model build (it costs seconds)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, SCRIPT], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode != 0
    assert proc.stdout.endswith("\n")
    lines = proc.stdout.splitlines()
    rec = _assert_contract(lines[-1])
    assert rec["ok"] is False
    assert rec["device"]["platform"] == "cpu"
    assert rec["device"]["count"] >= 1
    assert not any("checkpoint" in ln for ln in lines)  # failed before building
    assert "no TPU" in proc.stderr


def test_success_line_comes_from_the_same_function():
    smoke = _load()
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = smoke.final_line(True, device)
    assert line == (
        '{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", '
        '"count": 1}}'
    )
    ok, bad = _assert_contract(line), _assert_contract(
        smoke.final_line(False, smoke.NO_DEVICE)
    )
    assert ok["ok"] is True and bad["ok"] is False
    # an extra key in the device record never reaches the line
    noisy = dict(device, process_index=0)
    assert smoke.final_line(True, noisy) == line
