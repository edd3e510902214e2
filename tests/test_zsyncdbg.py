"""tsan-lite runtime sanitizer (utils/syncdbg.py): instrumented
Lock/RLock/Condition/Thread wrappers, inversion-on-second-edge,
hold-while-blocking, teardown unjoined-thread check, the deadlock
watchdog's cycle naming + all-stack dump, journal/metric plumbing, a
seeded inversion between LIVE components, the runtime-graph dump and
the `--compare-runtime` static-vs-runtime diff, and (slow) the PR 7
SLO soak under PDTT_SANITIZE=1 asserting zero findings end-to-end."""

import io
import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.registry import get_registry  # noqa: E402
from pytorch_distributed_train_tpu.utils import syncdbg  # noqa: E402


@pytest.fixture()
def sandbg():
    """Activated sanitizer with tight thresholds; restored after."""
    syncdbg.reset()
    syncdbg.activate(block_s=0.15, deadlock_s=0.6, watchdog_poll_s=0.05)
    yield syncdbg
    syncdbg.deactivate()
    syncdbg.reset()


def _two_locks():
    # NOTE: separate lines — lock identity is the creation site
    a = threading.Lock()
    b = threading.Lock()
    return a, b


# ------------------------------------------------------------- wrappers
def test_factories_are_patched_and_restored(sandbg):
    lk = threading.Lock()
    assert type(lk).__name__ == "SanLock"
    assert isinstance(threading.Thread(target=int), syncdbg.Thread)
    syncdbg.deactivate()
    assert type(threading.Lock()).__name__ != "SanLock"
    syncdbg.activate(block_s=0.15, deadlock_s=0.6, watchdog_poll_s=0.05)


def test_queue_event_condition_still_work(sandbg):
    import queue

    q = queue.Queue()
    q.put("x")
    assert q.get(timeout=1) == "x"
    ev = threading.Event()
    ev.set()
    assert ev.wait(0.2)
    cond = threading.Condition()
    with cond:
        cond.notify_all()
    got = []
    t = threading.Thread(
        target=lambda: got.append(q.get(timeout=2)), daemon=True)
    t.start()
    q.put("y")
    t.join(timeout=3)
    assert got == ["y"]


def test_condition_wait_without_lock_raises_without_corruption(sandbg):
    """wait() on an un-acquired Condition raises (stdlib contract) and
    must NOT fabricate a held-stack entry — later acquisitions would
    otherwise grow phantom lock-order edges from the never-held lock."""
    cond = threading.Condition()
    with pytest.raises(RuntimeError):
        cond.wait(0.1)
    a = threading.Lock()
    b = threading.Lock()
    with a:
        with b:
            pass
    assert list(syncdbg.edges()) == [(a.site, b.site)]
    assert syncdbg.findings() == []


# ------------------------------------------------------------ inversion
def test_inversion_fires_on_second_edge_direction_only(sandbg):
    a, b = _two_locks()
    with a:
        with b:
            pass
    assert syncdbg.findings("lock_inversion") == []  # one direction: fine
    with b:
        with a:
            pass
    inv = syncdbg.findings("lock_inversion")
    assert len(inv) == 1
    # both acquisition paths are named (sites + the reverse stack)
    assert "acquired while holding" in inv[0].message
    assert inv[0].detail["reverse_stack"]
    # the SAME inversion does not re-report on repetition
    with b:
        with a:
            pass
    assert len(syncdbg.findings("lock_inversion")) == 1


def test_inversion_between_live_components(sandbg):
    """Acceptance: a seeded inversion in LIVE components — a serving
    ReplicaSet's lock against a ckpt RamTier's lock, taken in both
    orders — is flagged with both creation sites named."""
    from pytorch_distributed_train_tpu.ckpt.hot_tier import RamTier
    from pytorch_distributed_train_tpu.serving_plane.router import (
        ReplicaSet,
    )

    rs = ReplicaSet()
    ram = RamTier()
    assert type(rs._lock).__name__ == "SanLock"  # born post-activation
    with rs._lock:
        with ram._lock:
            pass
    assert syncdbg.findings("lock_inversion") == []
    with ram._lock:
        with rs._lock:
            pass
    inv = syncdbg.findings("lock_inversion")
    assert len(inv) == 1
    msg = inv[0].message
    assert "serving_plane/router.py" in msg
    assert "ckpt/hot_tier.py" in msg


def test_findings_counted_and_journaled(sandbg, tmp_path):
    events_lib.configure(str(tmp_path))
    reg = get_registry()
    before = reg.family_total("sanitizer_findings_total")
    a, b = _two_locks()
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    assert reg.family_total("sanitizer_findings_total") == before + 1
    events_lib._reset_for_tests()  # close the sink before reading
    recs = [r for r in events_lib.load_events(str(tmp_path))
            if r["category"] == "sanitizer"]
    assert len(recs) == 1 and recs[0]["name"] == "lock_inversion"


# ------------------------------------------------- blocking while holding
def test_hold_while_blocking(sandbg):
    held = threading.Lock()
    contested = threading.Lock()
    release = threading.Event()

    def holder():
        with contested:
            release.wait(2.0)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    time.sleep(0.05)
    with held:
        got = contested.acquire(timeout=1.5)  # blocks ~0.3s > block_s
        time.sleep(0.0)
        release.set()
    # un-wedge:
    if got:
        contested.release()
    t.join(timeout=2)
    hw = syncdbg.findings("hold_while_blocking")
    assert hw, syncdbg.findings()
    assert "while holding" in hw[0].message


def test_fast_acquire_under_lock_is_fine(sandbg):
    a, b = _two_locks()
    with a:
        with b:
            pass
    assert syncdbg.findings("hold_while_blocking") == []


# ---------------------------------------------------------- watchdog
def test_deadlock_watchdog_dumps_and_names_cycle(sandbg, capfd):
    e = threading.Lock()
    f = threading.Lock()

    def t1():
        with e:
            time.sleep(0.15)
            f.acquire(timeout=2.5)

    def t2():
        with f:
            time.sleep(0.15)
            e.acquire(timeout=2.5)

    th1 = threading.Thread(target=t1, daemon=True)
    th2 = threading.Thread(target=t2, daemon=True)
    th1.start()
    th2.start()
    deadline = time.monotonic() + 4.0
    while not syncdbg.findings("deadlock") and time.monotonic() < deadline:
        time.sleep(0.05)
    th1.join(timeout=4)
    th2.join(timeout=4)
    dl = syncdbg.findings("deadlock")
    assert dl, "watchdog never fired"
    assert "wait-for cycle" in dl[0].message
    assert len(dl[0].detail["cycle"]) == 2  # the two lock sites
    err = capfd.readouterr().err
    assert "all-thread stack dump" in err
    assert "syncdbg-watchdog" in err  # every thread's stack is there


def test_idle_condition_waiter_is_not_a_deadlock(sandbg):
    """A consumer parked on its own condition holding nothing (the
    persister between persists) must NOT trip the watchdog."""
    cond = threading.Condition()
    stop = threading.Event()

    def consumer():
        with cond:
            cond.wait(timeout=1.2)

    t = threading.Thread(target=consumer, daemon=True)
    t.start()
    time.sleep(0.9)  # > deadlock_s while it waits
    stop.set()
    t.join(timeout=3)
    assert syncdbg.findings("deadlock") == []


# ---------------------------------------------------------- teardown
def test_unjoined_nondaemon_thread_flagged_at_teardown(sandbg):
    t = threading.Thread(target=lambda: None)
    t.start()
    while t.is_alive():
        time.sleep(0.01)
    new = syncdbg.check_teardown()
    assert [f.kind for f in new] == ["unjoined_thread"]
    assert "never joined" in new[0].message
    assert new[0].detail["site"].startswith("tests/test_zsyncdbg.py")
    # one report per thread: a second sweep stays quiet
    assert syncdbg.check_teardown() == []
    t.join()


def test_daemon_and_joined_threads_pass_teardown(sandbg):
    d = threading.Thread(target=lambda: None, daemon=True)
    d.start()
    j = threading.Thread(target=lambda: None)
    j.start()
    j.join()
    assert syncdbg.check_teardown() == []


# ----------------------------------------------------- compare-runtime
def test_dump_graph_roundtrip(sandbg, tmp_path):
    a, b = _two_locks()
    with a:
        with b:
            pass
    path = syncdbg.dump_graph(str(tmp_path / "g.json"))
    data = json.load(open(path))
    assert data["format"] == "pdtt-syncdbg-graph-v1"
    assert len(data["edges"]) == 1
    e = data["edges"][0]
    assert e["from"].startswith("tests/test_zsyncdbg.py:")
    assert e["count"] == 1 and e["stack"]


def _static_edge_sites():
    """One (from_site, to_site) pair for a statically-known edge, and
    the two nodes' sites for fabricating a reverse (unknown) edge."""
    from tools.analyze import core
    from tools.analyze.passes import lock_order

    g = lock_order.build_graph(core.build_context(REPO))
    assert g.edges, "static lock graph is empty?"
    (a, b) = sorted(g.edges)[0]
    site = {n: f"{g.nodes[n][0][0]}:{g.nodes[n][0][1]}" for n in (a, b)}
    return site[a], site[b]


def test_compare_runtime_covered_edge_exits_0(tmp_path):
    from tools.analyze import cli

    sa, sb = _static_edge_sites()
    graph = {"format": "pdtt-syncdbg-graph-v1",
             "edges": [{"from": sa, "to": sb, "count": 3,
                        "thread": "t", "stack": []}]}
    p = tmp_path / "g.json"
    p.write_text(json.dumps(graph))
    out = io.StringIO()
    rc = cli.main(["--only", "lock-order", "--compare-runtime", str(p)],
                  out=out)
    assert rc == 0, out.getvalue()
    assert "1 covered statically" in out.getvalue()


def test_compare_runtime_gap_exits_1(tmp_path):
    """A runtime edge the AST pass cannot see (here: the REVERSE of a
    static edge — never taken statically) is a named pass gap."""
    from tools.analyze import cli

    sa, sb = _static_edge_sites()
    graph = {"format": "pdtt-syncdbg-graph-v1",
             "edges": [{"from": sb, "to": sa, "count": 1,
                        "thread": "t", "stack": []}]}
    p = tmp_path / "g.json"
    p.write_text(json.dumps(graph))
    out = io.StringIO()
    rc = cli.main(["--only", "lock-order", "--compare-runtime", str(p)],
                  out=out)
    assert rc == 1
    assert "GAP" in out.getvalue()
    assert "invisible to lock-order" in out.getvalue()


def test_compare_runtime_foreign_and_unknown_locks(tmp_path):
    from tools.analyze import cli

    sa, _sb = _static_edge_sites()
    graph = {"edges": [
        # a lock born outside the analyzed surface: skipped, not a gap
        {"from": "tests/test_x.py:1", "to": "tests/test_x.py:2",
         "count": 1, "thread": "t", "stack": []},
        # an on-surface creation site the pass has no node for: a gap
        {"from": sa,
         "to": "pytorch_distributed_train_tpu/obs/collector.py:1",
         "count": 1, "thread": "t", "stack": []},
    ]}
    p = tmp_path / "g.json"
    p.write_text(json.dumps(graph))
    out = io.StringIO()
    rc = cli.main(["--only", "lock-order", "--compare-runtime", str(p)],
                  out=out)
    assert rc == 1
    text = out.getvalue()
    assert "1 skipped" in text
    assert "UNKNOWN to lock-order" in text


def test_compare_runtime_unreadable_graph_exits_2(tmp_path):
    from tools.analyze import cli

    p = tmp_path / "nope.json"
    assert cli.main(["--only", "lock-order", "--compare-runtime",
                     str(p)], out=io.StringIO()) == 2


# ------------------------------------------------------- sanitized soak
@pytest.mark.slow
def test_slo_soak_under_sanitizer_zero_findings():
    """THE sanitized-soak acceptance: the PR 7 SLO soak end-to-end
    under PDTT_SANITIZE=1 — all reliability bounds hold AND the
    sanitizer reports zero findings."""
    env = dict(os.environ)
    env.update({"PDTT_SANITIZE": "1", "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + os.path.join(REPO, "tools")})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "slo_soak.py"),
         "--requests", "300", "--clients", "8", "--seed", "7"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sanitizer_findings: 0" in r.stdout
    assert "all bounds held" in r.stdout
