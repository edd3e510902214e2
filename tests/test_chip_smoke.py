"""chip_smoke.py never reports a CPU as the chip.

The script is the chip run's entry point; here it is driven the way the
first rehearsal of the on-chip-measurement guide does: as its own process
under JAX_PLATFORMS=cpu (the suite's environment) with one CPU device,
as the one-chip machine has one chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, cwd=REPO, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *argv],
        cwd=cwd, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})


def test_refuses_without_a_tpu_and_prints_no_result():
    r = _run()
    assert r.returncode not in (0, 3), r.stderr[-2000:]
    assert r.stdout.strip() == ""  # no phase line, no result line
    assert "no TPU" in r.stderr
    r4 = _run("--four-chips")
    assert r4.returncode not in (0, 3) and r4.stdout.strip() == ""


def test_fails_in_a_directory_that_holds_nothing_else(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as src:
        (tmp_path / "chip_smoke.py").write_text(src.read())
    r = _run(cwd=str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.usefixtures("compile_cache_off")
def test_tiny_rehearsal_runs_every_phase_and_never_says_ok():
    r = _run("--tiny")
    assert r.returncode == 3, (r.stdout[-3000:], r.stderr[-3000:])
    assert '"ok": true' not in r.stdout
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu",
        "count": lines[-1]["device"]["count"]}}
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert list(phases) == ["train", "export_serve", "resnet50",
                            "attention_ab", "total"]
    assert phases["train"]["resumed_at"] == 6
    assert phases["train"]["last_loss"] < phases["train"]["first_loss"]
    assert phases["export_serve"]["requests"] == 4
    # this test's children run with the compile cache off (conftest's
    # compile_cache_off), as every child of the suite did before PR 44
    assert phases["total"]["cache_dir"] is None
