"""CI-style collection guard (ADVICE round 5, high): a single module
with an import-time error aborts the ENTIRE pytest run ("Interrupted: 1
error during collection" — 547 tests never ran because of one missing
``import functools``). This test collects the suite in a subprocess and
fails loudly on any collection error, so the next such typo costs one
red test instead of the whole round's signal."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_suite_collects_cleanly():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-p", "no:cacheprovider", "tests/"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, (
        "pytest collection failed:\n" + r.stdout[-3000:] + r.stderr[-2000:])
