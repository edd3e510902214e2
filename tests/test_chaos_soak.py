"""Chaos soak (tools/chaos_soak.py — ISSUE 2 satellite): a short
training job under a randomized (seeded) multi-fault schedule must
complete with nonzero retries and a verified final checkpoint. Runs as
a subprocess so the process-global fault schedule and metric counters
are isolated from the rest of the suite."""

import json
import os
import subprocess
import sys

import pytest

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_shrink_drill_resumes_degraded_with_verified_ckpt(tmp_path):
    """tools/chaos_soak.py --shrink (docs/elastic.md): kill one node
    permanently mid-run; the survivor re-rendezvouses degraded, resumes
    resharded with a monotone step count, completes the horizon, and
    the final checkpoint verifies. Subprocess for schedule/registry
    isolation, like the soak."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    env.pop("RESTART_GENERATION", None)
    env.pop("PDTT_FAULTS", None)
    env.pop("PDTT_EVENTS_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_soak.py"),
         "--shrink", "--seed", "0", "--steps", "6", "--out",
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["ok"] is True
    assert report["rcs"] == {"0": 0, "1": 45}  # rc 45 = permanent loss
    assert report["completed"] and report["monotone"]
    assert report["final_good_step"] == 6
    assert report["final_manifest_verified"] is True
    assert report["reshard_event"] and report["rendezvous_degraded"]


@pytest.mark.slow
def test_chaos_soak_completes_with_retries_and_verified_ckpt(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    env.pop("RESTART_GENERATION", None)
    env.pop("PDTT_FAULTS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_soak.py"),
         "--seed", "0", "--steps", "8", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-1200:], r.stderr[-1200:])
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["ok"] is True
    assert report["faults_injected_total"] > 0  # chaos actually happened
    assert report["retries_total"] > 0          # and was absorbed in place
    assert report["final_good_step"] == 8
    assert report["final_manifest_verified"] is True
