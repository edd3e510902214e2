"""Test harness: 8 fake CPU devices in one process (SURVEY §4.2).

The JAX analogue of torch's Gloo/fake-pg test backends
(torch:testing/_internal/common_distributed.py:874): all mesh/sharding tests
run the REAL jit'd train step on a virtual 8-device CPU mesh — no cluster,
no TPU. Both the env (for the subprocesses tests spawn) and the live jax
config are pinned to the CPU before any backend is instantiated, and the
persistent compile cache is off for the suite and its children: xdist
workers and the kill drills must never share one
(utils/compile_cache.py).
"""

import faulthandler
import os
import signal

# The -q suite occasionally dies SILENTLY (~13% of full runs): no
# traceback, no failing test name — just a truncated dot line. Leave a
# corpse next time: faulthandler catches hard crashes (SIGSEGV/SIGABRT
# — e.g. a poisoned XLA compile-cache entry), the SIGTERM hook catches
# the tier-1 `timeout` kill (dump every thread's stack, then chain to
# the previous disposition), and PDTT_TEST_DUMP_AFTER_S arms a one-shot
# all-stacks dump shortly before a known wall-clock cap (e.g. 840 for
# the 870s tier-1 budget) so a WEDGED test names itself even if the
# SIGTERM never lands. Best-effort: a test that installs its own
# SIGTERM handler in-process (preemption drills) overrides the hook.
faulthandler.enable()
try:
    faulthandler.register(signal.SIGTERM, chain=True)
except (AttributeError, ValueError, OSError):
    pass  # platform without register(), or not the main thread
_dump_after = os.environ.get("PDTT_TEST_DUMP_AFTER_S")
if _dump_after:
    try:
        faulthandler.dump_traceback_later(float(_dump_after), exit=False)
    except ValueError:
        pass

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# PDTT_SANITIZE=1: patch threading with the tsan-lite wrappers
# (utils/syncdbg.py) for the whole test process — after the jax import
# on purpose, so jax's own import-time locks stay real and findings
# point at OUR code. The sanitized soak test runs this way end-to-end.
from pytorch_distributed_train_tpu.utils import syncdbg as _syncdbg  # noqa: E402,I001

_syncdbg.maybe_activate()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"need 8 fake CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def tmp_ckpt_dir(tmp_path):
    return str(tmp_path / "ckpt")
