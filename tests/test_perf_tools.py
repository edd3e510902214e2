"""Host-side perf-evidence tools stay alive.

Each test drives the real CLI in a subprocess under JAX_PLATFORMS=cpu
and asserts the machine-readable contract, so a jax upgrade or refactor
that silently breaks a tool fails the suite. Compiles for the described
TPU live in tests/test_tpu_compile.py (in-process, one file: only one
process at a time may load the TPU library).
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tool(name: str, *argv: str, timeout: int = 900):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", name), *argv],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=ROOT)
    assert r.stdout.strip(), r.stderr[-2000:]
    return r, json.loads(r.stdout.strip().splitlines()[-1])


def test_spec_soak_index_is_sublinear():
    # (200 rounds of 32 slots: at 40 of 8 the ratio is taken from windows
    # of 0.2 ms, and one preemption on a loaded box read as 22x; past 256
    # rounds the appended tokens repeat and the rescan finds them at once)
    r, out = _run_tool("spec_soak.py", "--rounds", "200", "--slots", "32")
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["index_sublinear"] is True
    # and the rescan it replaced really does scale with context — the
    # comparison is the point of the tool
    assert out["rescan_8k_over_512"] > 4.0
