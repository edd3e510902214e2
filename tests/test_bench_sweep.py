"""bench_sweep tool: battery definition stays valid and the runner
produces a parseable incremental report."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dry_run_lists_every_arm():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_sweep.py"),
         "--dry-run"], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    lines = [ln for ln in out.stdout.splitlines() if ": python bench.py" in ln]
    assert len(lines) >= 15
    assert any("resnet50_baseline" in ln for ln in lines)
    assert any("serve_prefix_fork" in ln for ln in lines)
    # extra arms (hardware-evidence probes) are listed too
    assert "llama7b_geometry_step" in out.stdout


def test_tiny_arm_produces_report(tmp_path):
    report = tmp_path / "sweep.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_sweep.py"),
         "--tiny", "--only", "llama_decode_int8", "--timeout", "300",
         "--out", str(report)],
        capture_output=True, text=True, timeout=400,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(report.read_text())["llama_decode_int8"]
    assert rec["rc"] == 0
    assert rec["parsed"]["metric"].startswith("llama_decode_int8_tiny")
    assert rec["parsed"]["value"] > 0


def test_unknown_filter_is_loud():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_sweep.py"),
         "--only", "nonexistent_arm_xyz"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 2
    assert "no arms match" in out.stderr
