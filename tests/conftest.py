"""Test harness: 8 fake CPU devices in one process (SURVEY §4.2).

The JAX analogue of torch's Gloo/fake-pg test backends
(torch:testing/_internal/common_distributed.py:874): all mesh/sharding tests
run the REAL jit'd train step on a virtual 8-device CPU mesh — no cluster,
no TPU. Both the env (for the subprocesses tests spawn) and the live jax
config are pinned to the CPU before any backend is instantiated, and the
persistent compile cache is off for the suite and its children: xdist
workers and the kill drills must never share one
(utils/compile_cache.py).

Every test has one limit, ``TEST_LIMIT_S``: a drill that wedges fails
alone, with every thread's stack, and takes its child processes with it,
instead of holding its worker until the run's own ``timeout`` kills
everything and the count becomes "how far the run got".
"""

import faulthandler
import os
import signal
import sys
import threading
import traceback

# A hard crash (SIGSEGV, or XLA's own abort) leaves every thread's Python
# stack; so does the SIGTERM of the tier-1 ``timeout``, which then chains
# to the previous disposition. Best-effort: a test that installs its own
# SIGTERM handler in-process (the preemption drills) overrides the hook.
faulthandler.enable()
try:
    faulthandler.register(signal.SIGTERM, chain=True)
except (AttributeError, ValueError, OSError):
    pass  # platform without register(), or not the main thread

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

import psutil  # noqa: E402
import pytest  # noqa: E402

TEST_LIMIT_S = 300


def _all_stacks() -> str:
    names = {t.ident: t.name for t in threading.enumerate()}
    return "\n".join(
        f"--- thread {names.get(ident, ident)}\n"
        + "".join(traceback.format_stack(frame, limit=16))
        for ident, frame in sys._current_frames().items())


@pytest.fixture(autouse=True)
def _test_limit():
    """Fail the test when it has run ``TEST_LIMIT_S``: the alarm raises in
    the main thread (a sleep, a wait on a process or a lock all return to
    it), with the stacks as they stood. On that way out the children the
    test started are killed, whole trees: a drill's own ``finally`` may
    never have been reached."""
    me = psutil.Process()
    before = {p.pid for p in me.children(recursive=True)}
    fired = []

    def on_alarm(signum, frame):
        fired.append(True)
        pytest.fail(f"test ran past its {TEST_LIMIT_S} s limit\n"
                    + _all_stacks(), pytrace=False)

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        if fired:
            mine = [p for p in me.children(recursive=True)
                    if p.pid not in before]
            for p in mine:
                try:
                    p.kill()
                except psutil.NoSuchProcess:
                    pass
            psutil.wait_procs(mine, timeout=10)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"need 8 fake CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def tmp_ckpt_dir(tmp_path):
    return str(tmp_path / "ckpt")
