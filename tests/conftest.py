"""Test harness: 8 fake CPU devices in one process (SURVEY §4.2).

The JAX analogue of torch's Gloo/fake-pg test backends
(torch:testing/_internal/common_distributed.py:874): all mesh/sharding tests
run the REAL jit'd train step on a virtual 8-device CPU mesh — no cluster,
no TPU. Both the env (for the subprocesses tests spawn) and the live jax
config are pinned to the CPU before any backend is instantiated.

What the harness guarantees, to every test and to the children it starts
with the environment it inherits:

- One limit a test, ``TEST_LIMIT_S``: a drill that wedges fails alone, with
  every thread's stack, and takes its child processes with it, instead of
  holding its worker until the run's own ``timeout`` kills everything and
  the count becomes "how far the run got".
- The CPU compiler at little effort (``XLA_FLAGS``: backend optimization
  level 1, LLVM's expensive passes off). Most of a run is XLA compiling for
  the CPU, which is the suite's vehicle and nobody's target; every
  tolerance, hash and text the tests read holds under it (level 0 compiles
  a tenth faster still and moves one float32 sum past its tolerance). A
  child a test starts with ``XLA_FLAGS`` of its own (the drills' one-device
  workers) compiles as a user's process does.
- Each program is compiled once a RUN, not once a process: the process
  that starts the run makes a fresh directory under the temporary root and
  it is the run's persistent compile cache, for the six xdist workers and
  for every child a test starts with the environment it inherits (every
  program goes in: ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0``). It
  lives for the run: ``pytest_unconfigure`` removes it, its name is never
  used twice, so a run's time never depends on what an earlier tree left on
  the box. Inside a process JAX's own jit cache and the builders the test
  files memoize (``functools.lru_cache``: ``tests/lm_family.py``'s
  ``family``, ``test_ema``'s ``_compiled``) do the rest: a worker handed
  tests of one file in several runs (``--dist load``) builds their subject
  once. A set ``JAX_COMPILATION_CACHE_DIR`` wins over a trainer's own
  preference (utils/compile_cache.py), so ``obs.compile_cache_dir`` and the
  launcher's per-worker directories decide nothing here.
- A test that ends a process abruptly (SIGKILL, SIGTERM, ``os._exit``,
  ``.kill()``, a fault that crashes a worker) asks for ``compile_cache_off``
  (``pytestmark = pytest.mark.usefixtures("compile_cache_off")``): a cache
  write cut short leaves a truncated entry, which JAX does not check before
  it loads it (utils/compile_cache.py), and every later reader of that
  program would meet it. Under the fixture the test and its children run
  with no persistent cache, as the whole suite did before PR 44. So does a
  test about the cache itself (test_compile_cache, test_determinism), which
  gives it a directory under its own ``tmp_path``.
- A bound on what a worker holds, ``WORKER_RSS_BOUND``: past it at a test's
  end the process drops JAX's compiled programs and returns the pages.
"""

import ctypes
import faulthandler
import gc
import os
import shutil
import signal
import sys
import tempfile
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
    _flags += " --xla_force_host_platform_device_count=8"
# (the module docstring's second guarantee)
for _flag in ("--xla_backend_optimization_level=1",
              "--xla_llvm_disable_expensive_passes=true"):
    if _flag.split("=")[0] not in _flags:
        _flags += " " + _flag
os.environ["XLA_FLAGS"] = _flags.strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# (the docstring's third guarantee) The process that starts the run makes the
# run's compile cache; its xdist workers, and every child a test starts,
# find it in the environment. Every program goes in, however small or quick.
_run_cache = None
if "PYTEST_XDIST_WORKER" not in os.environ:
    _run_cache = tempfile.mkdtemp(prefix="pdtt_run_compile_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _run_cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "true"
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

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
WORKER_RSS_BOUND = 4 << 30


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


def pytest_unconfigure(config):
    if _run_cache:  # nothing a run compiled outlives it
        shutil.rmtree(_run_cache, ignore_errors=True)


@pytest.fixture()
def compile_cache_off(monkeypatch):
    """The run's compile cache off for this test and for the children it
    starts, as the whole suite ran before PR 44. For a test that ends a
    process abruptly (a cache write cut short leaves a truncated entry, which
    every later reader of that program meets) and for a test about the cache
    itself: ``pytestmark = pytest.mark.usefixtures("compile_cache_off")``."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "false")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # (JAX decides once whether it caches)
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _worker_memory_bound():
    """Past ``WORKER_RSS_BOUND`` at a test's end the process drops every
    program JAX has compiled and hands the freed pages back: what a worker
    holds is mostly its jit caches (3-4 MB a tiny program; 5-8 GiB a
    worker by the end of a run, 35 GiB over six, before this bound), and a
    box that runs out ends the largest processes it finds, which reads as
    ``node down`` against whatever test came next. A later test that calls
    a program of an earlier one compiles it again."""
    yield
    if psutil.Process().memory_info().rss > WORKER_RSS_BOUND:
        jax.clear_caches()
        gc.collect()
        ctypes.CDLL("libc.so.6").malloc_trim(0)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"need 8 fake CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def tmp_ckpt_dir(tmp_path):
    return str(tmp_path / "ckpt")
