"""Multi-replica router (serving_plane/router.py + tools/serve_router.py):
least-outstanding balancing, health-probe state flips, failover to a
survivor, session pinning, hedging of stragglers, rolling restart with
zero failed requests, store-based replica discovery, and the ISSUE-7
acceptance drill (subprocess replicas: injected slow decode → anomaly +
fake profiler capture + hedging; SIGTERM → drain → failover; deadline →
504 with slots reclaimed; timeline shows the chain)."""

import json
import os
import queue as queue_mod
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import ThreadingHTTPServer

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import serve_http  # noqa: E402
import serve_router as serve_router_tool  # noqa: E402

from pytorch_distributed_train_tpu.faults import (  # noqa: E402
    registry as fregistry,
)
from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.events import load_events  # noqa: E402
from pytorch_distributed_train_tpu.obs.registry import get_registry  # noqa: E402
from pytorch_distributed_train_tpu.serving_plane import (  # noqa: E402
    ReliabilityPlane,
)
from pytorch_distributed_train_tpu.serving_plane.router import (  # noqa: E402
    HealthProber,
    ReplicaSet,
    Router,
)
from pytorch_distributed_train_tpu.serving_plane.testing import (  # noqa: E402
    FakeByteTok,
    FakeTokenBatcher,
)

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")


@pytest.fixture(autouse=True)
def _clean_planes():
    fregistry._reset_for_tests()
    yield
    fregistry._reset_for_tests()
    events_lib._reset_for_tests()


def _counter(name):
    return get_registry().get_value(name) or 0.0


def _make_replica(port=0, *, slots=4, step_delay_s=0.005,
                  drain_grace=10.0):
    batcher = FakeTokenBatcher(slots=slots, step_delay_s=step_delay_s)
    svc = serve_http.BatcherService(
        batcher, FakeByteTok(), plane=ReliabilityPlane(slots=slots),
        orphan_grace_s=0.5)
    httpd = ThreadingHTTPServer(("127.0.0.1", port), None)
    drain = serve_http.GracefulDrain(httpd, svc, grace_s=drain_grace)
    httpd.RequestHandlerClass = serve_http.make_handler(svc, drain)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return {"svc": svc, "httpd": httpd, "drain": drain,
            "batcher": batcher, "port": httpd.server_address[1],
            "addr": f"127.0.0.1:{httpd.server_address[1]}"}


def _kill_replica(rep):
    rep["httpd"].shutdown()
    rep["httpd"].server_close()
    rep["svc"].shutdown()


def _body(prompt="hello", max_tokens=4, **kw):
    d = {"prompt": prompt, "max_tokens": max_tokens, **kw}
    return json.dumps(d).encode(), d


# ----------------------------------------------------------------- units

def test_replicaset_pick_least_outstanding_and_states():
    rs = ReplicaSet(("a:1", "b:2"))
    assert rs.pick() == "a:1"  # tie → lexicographic
    rs.begin("a:1")
    assert rs.pick() == "b:2"  # least outstanding
    rs.mark("b:2", "draining")
    assert rs.pick() == "a:1"  # draining unroutable
    rs.mark("a:1", "down")
    assert rs.pick() is None
    rs.mark("a:1", "up")
    # a shedding replica ranks after a non-shedding one
    rs.mark("b:2", "up", healthz={"admission": "shedding"})
    rs.end("a:1")
    assert rs.pick() == "a:1"
    snap = {r["addr"]: r for r in rs.snapshot()}
    assert snap["b:2"]["admission"] == "shedding"


def test_prober_flips_states_and_journals(tmp_path):
    events_lib.configure(str(tmp_path))
    rs = ReplicaSet(("x:1",))
    answers = {"mode": "ok"}

    def fetch(addr):
        if answers["mode"] == "ok":
            return 200, {"status": "ok",
                         "reliability": {"admission": "ok",
                                         "queue_depth": 0}}
        if answers["mode"] == "draining":
            return 503, {"status": "draining"}
        raise OSError("connection refused")

    p = HealthProber(rs, down_after=2, fetch=fetch)
    p.probe_once()
    assert rs.get("x:1").state == "up"
    assert rs.get("x:1").healthz["admission"] == "ok"
    answers["mode"] = "draining"
    p.probe_once()
    assert rs.get("x:1").state == "draining"
    answers["mode"] = "dead"
    p.probe_once()  # one failed probe: debounced, still draining
    assert rs.get("x:1").state == "draining"
    p.probe_once()
    assert rs.get("x:1").state == "down"
    answers["mode"] = "ok"
    p.probe_once()
    assert rs.get("x:1").state == "up"
    names = [(e["category"], e["name"]) for e in load_events(str(tmp_path))]
    assert ("serve", "replica_down") in names
    assert ("serve", "replica_up") in names


def test_store_publish_and_discover_replicas():
    from pytorch_distributed_train_tpu.elastic import (
        discover_replicas,
        publish_replica,
    )
    from pytorch_distributed_train_tpu.native.store import (
        StoreClient,
        StoreServer,
    )

    with StoreServer() as srv:
        c = StoreClient("127.0.0.1", srv.port)
        assert discover_replicas(c) == []
        assert publish_replica(c, "127.0.0.1:8000") == 0
        assert publish_replica(c, "127.0.0.1:8001") == 1
        assert discover_replicas(c) == ["127.0.0.1:8000",
                                        "127.0.0.1:8001"]
        c.close()
    assert discover_replicas(None) == []


# ------------------------------------------------------------- failover

def test_router_fails_over_to_survivor(tmp_path):
    events_lib.configure(str(tmp_path), who="router")
    a, b = _make_replica(), _make_replica()
    rs = ReplicaSet((a["addr"], b["addr"]))
    prober = HealthProber(rs, interval_s=0.2)
    prober.probe_once()
    router = Router(rs, timeout_s=30.0)
    before = _counter("serve_failovers_total")
    try:
        _kill_replica(a)  # dead, but still marked up: the router's
        rs.begin(b["addr"])  # tiebreak must pick the corpse first
        raw, body = _body("failover me", 4)
        status, rbody = router.request("/v1/completions", raw, body)
        rs.end(b["addr"])
        assert status == 200, rbody
        assert json.loads(rbody)["finish_reason"] in ("length", "eos")
        assert _counter("serve_failovers_total") == before + 1
        names = [(e["category"], e["name"])
                 for e in load_events(str(tmp_path))]
        assert ("serve", "failover") in names
        # with A gone and probed, the set converges to B only
        prober.probe_once()
        prober.probe_once()
        assert rs.get(a["addr"]).state == "down"
        assert rs.pick() == b["addr"]
    finally:
        _kill_replica(b)


def test_session_pins_to_owning_replica():
    a, b = _make_replica(), _make_replica()
    rs = ReplicaSet((a["addr"], b["addr"]))
    HealthProber(rs).probe_once()
    router = Router(rs, timeout_s=30.0)
    try:
        raw, body = _body("turn one", 4, keep=True)
        status, rbody = router.request("/v1/completions", raw, body)
        assert status == 200
        sid = json.loads(rbody)["session"]
        assert sid is not None and router.sessions[sid] in (a["addr"],
                                                           b["addr"])
        # a resume routes HOME: the other replica would 400 it as an
        # unknown session, so a 200 proves the pin
        raw2, body2 = _body("turn two", 4, session=sid)
        status2, rbody2 = router.request("/v1/completions", raw2, body2)
        assert status2 == 200, rbody2
    finally:
        _kill_replica(a)
        _kill_replica(b)


def test_hedge_straggler_completes_on_second_replica(tmp_path):
    events_lib.configure(str(tmp_path), who="router")
    slow = _make_replica(step_delay_s=0.25)
    fast = _make_replica(step_delay_s=0.002)
    rs = ReplicaSet((slow["addr"], fast["addr"]))
    HealthProber(rs).probe_once()
    router = Router(rs, timeout_s=30.0, hedge_after_s=0.3)
    before = _counter("serve_hedges_total")
    try:
        rs.begin(fast["addr"])  # force the straggler to win the pick
        threading.Timer(0.1, rs.end, args=(fast["addr"],)).start()
        t0 = time.monotonic()
        raw, body = _body("straggling", 8)
        status, rbody = router.request("/v1/completions", raw, body)
        dt = time.monotonic() - t0
        assert status == 200
        # the slow replica would need >= 8 * 0.25 = 2s; the hedge won
        assert dt < 1.8, dt
        assert _counter("serve_hedges_total") == before + 1
        names = [(e["category"], e["name"])
                 for e in load_events(str(tmp_path))]
        assert ("serve", "hedge") in names
        assert ("serve", "hedge_win") in names
    finally:
        _kill_replica(slow)
        _kill_replica(fast)


# -------------------------------------------------- HTTP front (tool)

def test_router_tool_http_front_relays_and_streams():
    a, b = _make_replica(), _make_replica()
    rs = ReplicaSet((a["addr"], b["addr"]))
    prober = HealthProber(rs, interval_s=0.2)
    prober.probe_once()
    router = Router(rs, timeout_s=30.0)
    front = ThreadingHTTPServer(
        ("127.0.0.1", 0), serve_router_tool.make_handler(router, prober))
    threading.Thread(target=front.serve_forever, daemon=True).start()
    port = front.server_address[1]
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": "via the front",
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["finish_reason"] in ("length", "eos")
        # streamed passthrough ends with [DONE]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": "stream via front",
                             "max_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            raw = r.read().decode()
        assert raw.rstrip().endswith("data: [DONE]")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok" and health["up"] == 2
    finally:
        front.shutdown()
        front.server_close()
        _kill_replica(a)
        _kill_replica(b)


# ------------------------------------------------------ rolling restart

def test_rolling_restart_drains_with_zero_failed_requests(tmp_path):
    """Two supervised replicas, continuous traffic, rolling restart:
    every replica walks through the drain path, every request lands
    200 — the zero-failed-requests fleet restart."""
    events_lib.configure(str(tmp_path), who="router")
    boxes = [_make_replica(drain_grace=10.0), _make_replica(
        drain_grace=10.0)]
    stop = threading.Event()

    def supervise(box):
        # the "systemd" of this test: when the drain stops the service,
        # close the socket and bring a fresh replica up on the SAME port
        while not stop.is_set():
            if box["svc"]._stop:
                box["httpd"].server_close()
                time.sleep(1.0)  # let the router observe the death
                box.update(_make_replica(port=box["port"],
                                         drain_grace=10.0))
            time.sleep(0.05)

    sups = [threading.Thread(target=supervise, args=(b,), daemon=True)
            for b in boxes]
    for s in sups:
        s.start()
    rs = ReplicaSet(tuple(b["addr"] for b in boxes))
    prober = HealthProber(rs, interval_s=0.2)
    prober.start()
    router = Router(rs, timeout_s=30.0)
    statuses: list[int] = []
    lock = threading.Lock()

    def traffic():
        i = 0
        while not stop.is_set():
            raw, body = _body(f"rolling {i}", 3)
            status, _ = router.request("/v1/completions", raw, body)
            with lock:
                statuses.append(status)
            i += 1
            time.sleep(0.02)

    tthreads = [threading.Thread(target=traffic, daemon=True)
                for _ in range(2)]
    for t in tthreads:
        t.start()
    try:
        time.sleep(0.5)
        report = router.rolling_restart(down_timeout_s=20.0,
                                        wait_back_s=20.0)
        time.sleep(0.5)
    finally:
        stop.set()
        for t in tthreads:
            t.join(timeout=30)
        prober.stop()
    assert [e.get("drained") for e in report] == [True, True], report
    assert [e.get("back") for e in report] == [True, True], report
    assert statuses and all(s == 200 for s in statuses), (
        [s for s in statuses if s != 200][:5], len(statuses))
    names = [(e["category"], e["name"]) for e in load_events(str(tmp_path))]
    assert names.count(("serve", "rolling_drain")) == 2
    for b in boxes:
        _kill_replica(b)


# ----------------------------------------------------- acceptance drill

def _spawn_replica(tmp_path, name, *, faults="", extra_env=None,
                   extra_args=()):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "PDTT_EVENTS_DIR": str(tmp_path / "events"),
           "PDTT_PROFILE_BACKEND": "fake",
           "PDTT_PROFILE_DIR": str(tmp_path / f"prof_{name}"),
           **(extra_env or {})}
    if faults:
        env["PDTT_FAULTS"] = faults
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve_http.py"),
         "--fake-backend", "--fake-step-delay", "0.01", "--port", "0",
         "--slots", "4", "--profile-on-tail",
         "--tail-capture-seconds", "0.3", "--tail-cooldown", "5",
         "--drain-grace", "5", *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    q: queue_mod.Queue = queue_mod.Queue()

    def pump():
        for line in proc.stdout:
            q.put(line)

    threading.Thread(target=pump, daemon=True).start()
    deadline = time.monotonic() + 120.0
    port = None
    while time.monotonic() < deadline:
        try:
            line = q.get(timeout=max(0.1, deadline - time.monotonic()))
        except queue_mod.Empty:
            break
        m = re.search(r"serving on http://127\.0\.0\.1:(\d+)", line)
        if m:
            port = int(m.group(1))
            break
    assert port is not None, f"replica {name} never came up"
    return proc, f"127.0.0.1:{port}"


def test_e2e_drill_anomaly_hedge_drain_failover(tmp_path):
    """The ISSUE-7 acceptance drill: 2 replicas behind the router under
    continuous traffic; serve.slow_decode injected on replica A →
    tail-latency anomaly journaled + (fake-backend) profiler capture
    fires + hedged requests complete on B; SIGTERM A → drain → router
    fails over with zero failed requests; a deadline-expired request
    504s with its slot verifiably reclaimed; the merged journal +
    timeline_report show the anomaly→hedge→drain chain."""
    events_dir = tmp_path / "events"
    proc_a, addr_a = _spawn_replica(
        tmp_path, "a", faults="serve.slow_decode@call=30:count=25:"
                             "delay=0.4",
        extra_env={"PROCESS_ID": "1"})
    proc_b, addr_b = _spawn_replica(tmp_path, "b",
                                    extra_env={"PROCESS_ID": "2"})
    events_lib.configure(str(events_dir), who="router")
    rs = ReplicaSet((addr_a, addr_b))
    prober = HealthProber(rs, interval_s=0.5)
    prober.start()
    router = Router(rs, timeout_s=60.0, hedge_after_s=0.8)
    stop = threading.Event()
    failures: list[tuple[int, bytes]] = []
    lock = threading.Lock()

    def traffic(ci):
        i = 0
        while not stop.is_set():
            raw, body = _body(f"drill {ci}-{i}", 6)
            status, rbody = router.request("/v1/completions", raw, body)
            if status != 200:
                with lock:
                    failures.append((status, rbody[:200]))
            i += 1
            time.sleep(0.02)

    threads = [threading.Thread(target=traffic, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    try:
        # phase 1 — the slow-decode storm on A: wait until a hedge won
        # and A's anomaly journaled (both driven by the injected stalls)
        deadline = time.monotonic() + 60.0
        seen_hedge = seen_anomaly = False
        while time.monotonic() < deadline:
            names = [(e["category"], e["name"], e.get("host"))
                     for e in load_events(str(events_dir))]
            seen_hedge = any(n[:2] == ("serve", "hedge_win")
                             for n in names)
            seen_anomaly = any(
                n[0] == "anomaly" and n[2] == "host1"
                and n[1] in ("ttft_regression", "inter_token_regression")
                for n in names)
            if seen_hedge and seen_anomaly:
                break
            time.sleep(0.25)
        assert seen_anomaly, "no tail-latency anomaly journaled on A"
        assert seen_hedge, "no hedged completion won on B"
        # the anomaly fired the managed profiler (fake backend marker)
        cap_deadline = time.monotonic() + 20.0
        markers = []
        while time.monotonic() < cap_deadline and not markers:
            markers = [os.path.join(r, f)
                       for r, _d, fs in os.walk(tmp_path / "prof_a")
                       for f in fs if f == "FAKE_CAPTURE"]
            time.sleep(0.2)
        assert markers, "anomaly-triggered capture never materialized"
        # deadline-expired request → 504 through the router
        raw, body = _body("budget blown", 500, deadline_s=0.05)
        status, rbody = router.request("/v1/completions", raw, body)
        assert status == 504, (status, rbody)
        # phase 2 — SIGTERM A: graceful drain, router fails over
        proc_a.send_signal(signal.SIGTERM)
        assert proc_a.wait(timeout=60) == 0
        time.sleep(2.0)  # traffic keeps flowing through B
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert not failures, failures[:5]
        # slots verifiably reclaimed on the survivor: no leaks, all free
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with urllib.request.urlopen(f"http://{addr_b}/healthz",
                                        timeout=10) as r:
                health = json.loads(r.read())
            slots = health["reliability"]["slots"]
            if slots["active"] == 0 and slots["queued"] == 0:
                break
            time.sleep(0.2)
        assert slots["active"] == 0 and slots["queued"] == 0
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        prober.stop()
        for p in (proc_a, proc_b):
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
    events = load_events(str(events_dir))
    names = [(e["category"], e["name"]) for e in events]
    assert ("serve", "tail_latency") in names
    assert ("serve", "drain_begin") in names
    assert (("serve", "failover") in names
            or ("serve", "replica_down") in names)
    assert ("fault", "serve.slow_decode") in names  # the injection record
    # the cross-host timeline tells the story in one read
    import timeline_report

    text = "\n".join(timeline_report.timeline_lines(events, width=60))
    assert "tail_latency" in text and "drain_begin" in text
    chains = "\n".join(timeline_report.causal_chains(events))
    assert "-> capture" in chains, chains
