"""Closed-loop fleet controller (fleet/controller.py — ISSUE 17):
registry tombstones, the AlertEngine resolve-side incident-id contract,
router dispatch weights, the controller's safety rails (dry-run,
hysteresis, bounds, budget latch + re-arm, cooled double-act guards)
against fake collector/engine state, and the satellite drill:
controller-initiated scale-in under live load with zero failed
requests, session pinning respected, and the victim's slots verifiably
reclaimed. The full subprocess drill lives in test_zautoscale_drill.py
(slow)."""

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import serve_http  # noqa: E402

from pytorch_distributed_train_tpu.elastic import (  # noqa: E402
    SERVE_REPLICA_COUNT_KEY,
    discover_replicas,
    publish_replica,
    tombstone_replica,
)
from pytorch_distributed_train_tpu.faults import (  # noqa: E402
    registry as fregistry,
)
from pytorch_distributed_train_tpu.fleet.controller import (  # noqa: E402
    ACTIONS,
    OUTCOMES,
    POLICY_TRIGGERS,
    FleetController,
    ReplicaLauncher,
)
from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.alerts import (  # noqa: E402
    RULES,
    AlertEngine,
)
from pytorch_distributed_train_tpu.obs.collector import Target  # noqa: E402
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


@pytest.fixture(autouse=True)
def _clean_planes():
    fregistry._reset_for_tests()
    yield
    fregistry._reset_for_tests()
    events_lib._reset_for_tests()


# ------------------------------------------------------------- fakes

class _StubCollector:
    """What AlertEngine reads: targets + stale_after_s."""

    def __init__(self, targets, stale_after_s=5.0):
        self.targets = list(targets)
        self.stale_after_s = stale_after_s


class _FakeCollector:
    """What the controller reads: serving load rows."""

    def __init__(self, rows):
        self.rows = rows

    def serving_rows(self):
        return [dict(r) for r in self.rows]


class _FakeEngine:
    def __init__(self):
        self.alerts = []
        self.subs = []

    def subscribe(self, fn):
        self.subs.append(fn)

    def firing(self):
        return [dict(a) for a in self.alerts]


def _row(addr, host=None, queue_depth=0, state="ok", admission="ok",
         shed_per_s=0.0):
    return {"addr": addr, "host": host or addr.split(":")[0],
            "state": state, "role": "serving",
            "queue_depth": queue_depth, "admission": admission,
            "shed_per_s": shed_per_s}


def _alert(rule="shed_storm", host="h0"):
    return {"rule": rule, "role": "serving", "host": host,
            "for_s": 2.0, "value": 5.0, "baseline": 0.0,
            "id": f"{rule}@{host}@1234"}


class _StaticLauncher(ReplicaLauncher):
    """Hands out pre-arranged addresses; records every call."""

    def __init__(self, addrs=()):
        self.addrs = list(addrs)
        self.launched = []
        self.stopped = []

    def launch(self):
        addr = self.addrs.pop(0) if self.addrs else None
        if addr is not None:
            self.launched.append(addr)
        return addr

    def stop(self, addr):
        self.stopped.append(addr)


class _DrainRecorder(FleetController):
    """Controller whose drain actuator records instead of HTTP."""

    def __init__(self, *a, **kw):
        self.drains = []
        super().__init__(*a, **kw)

    def _do_drain(self, addr):
        self.drains.append(addr)
        with self._lock:
            self._drained[addr] = time.monotonic() + 60.0
        return "effective", {"addr": addr}


def _healthz_server():
    """A bare /healthz responder for verify-after-launch."""
    class _H(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            body = b'{"status": "ok"}'
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"127.0.0.1:{httpd.server_address[1]}"


def _actions(events_dir):
    return [(e["name"], e.get("detail", {}))
            for e in load_events(events_dir)
            if e["category"] == "action"]


_ZERO_COOLDOWNS = {"scale_out": 0.0, "scale_in": 0.0, "recycle": 0.0,
                   "rebalance": 0.0}


# --------------------------------------------------- registry tombstones

def test_registry_tombstone_skips_cleanly_exited_replica():
    from pytorch_distributed_train_tpu.native.store import (
        StoreClient,
        StoreServer,
    )

    with StoreServer() as srv:
        c = StoreClient("127.0.0.1", srv.port)
        i0 = publish_replica(c, "127.0.0.1:8000")
        publish_replica(c, "127.0.0.1:8001")
        assert discover_replicas(c) == ["127.0.0.1:8000",
                                        "127.0.0.1:8001"]
        # clean exit writes a tombstone: the address disappears from
        # discovery forever — fleet-size math stops over-counting
        assert tombstone_replica(c, i0) is True
        assert discover_replicas(c) == ["127.0.0.1:8001"]
        # a later replica claims a NEW index past the tombstone
        assert publish_replica(c, "127.0.0.1:8002") == 2
        assert discover_replicas(c) == ["127.0.0.1:8001",
                                        "127.0.0.1:8002"]
        assert int(c.add(SERVE_REPLICA_COUNT_KEY, 0)) == 3
        c.close()
    assert tombstone_replica(None, 0) is False  # storeless: best-effort


# ------------------------------------- alert resolve-side id contract

class _TestClock:
    t = time.monotonic()


def _push(t, series, *values):
    for v in values:
        _TestClock.t += 1e-3
        t.series[series].append((_TestClock.t, float(v)))


def test_alert_resolve_carries_incident_id_and_notifies_subscribers(
        tmp_path):
    events_lib.configure(str(tmp_path))
    t = Target({"role": "trainer", "host": "host0",
                "addr": "127.0.0.1:1", "gen": "0", "idx": 0})
    col = _StubCollector([t])
    engine = AlertEngine(overrides={"loss_spike.min_samples": 4})
    seen = []
    engine.subscribe(lambda rec: 1 / 0)  # actuator bug: swallowed
    engine.subscribe(seen.append)
    _push(t, "loss", 2.0, 2.1, 1.9, 2.0, 2.05)
    assert engine.evaluate(col) == []
    _push(t, "loss", 2e6)
    trans = engine.evaluate(col)
    assert [r["event"] for r in trans] == ["fired"]
    fid = trans[0]["id"]
    assert fid and fid.startswith("loss_spike@host0@")
    assert engine.firing()[0]["id"] == fid
    _push(t, "loss", 2.0, 2.0)
    trans = engine.evaluate(col)
    assert [r["event"] for r in trans] == ["resolved"]
    # the contract under test: resolve carries the SAME incident id,
    # no caller-side rule@host@ms string reconstruction
    assert trans[0]["id"] == fid
    # subscribers got both transitions despite the broken one ahead
    assert [r["event"] for r in seen] == ["fired", "resolved"]
    assert all(r["id"] == fid for r in seen)
    journal = [(e["name"], e["detail"].get("id"))
               for e in load_events(str(tmp_path))
               if e["category"] == "alert"]
    assert ("fired", fid) in journal and ("resolved", fid) in journal


# ------------------------------------------------- router weights hook

def test_router_weights_and_role_aware_dispatch():
    rs = ReplicaSet(("a:1", "b:2"))
    rs.begin("a:1")  # a:1 outstanding=1, b:2 idle → b wins
    assert rs.pick() == "b:2"
    # weights divide effective load: (1+1)/4.0 < (0+1)/0.2
    rs.set_weights({"a:1": 4.0, "b:2": 0.2})
    assert rs.pick() == "a:1"
    rs.set_weights({"a:1": 0.0, "b:2": -3.0})  # non-positive: ignored
    snap = {r["addr"]: r for r in rs.snapshot()}
    assert snap["a:1"]["weight"] == 4.0 and snap["b:2"]["weight"] == 0.2
    # role-aware stub: a matching pool is preferred, mixed serves all
    rs.add("c:3", role="prefill")
    rs.begin("c:3")
    rs.begin("c:3")
    assert rs.pick(role="prefill") == "c:3"  # loaded, but role-matched
    assert rs.pick(role="decode") in ("a:1", "b:2")  # no pool: weights


# ----------------------------------------------------- controller rails

def test_catalog_is_closed_and_well_formed():
    for spec in ACTIONS.values():
        assert set(spec.outcomes) <= set(OUTCOMES)
        assert "requested" in spec.outcomes
        for t in spec.triggers:
            assert t in RULES or t in POLICY_TRIGGERS, t


def test_dry_run_journals_intent_and_acts_nothing(tmp_path):
    events_lib.configure(str(tmp_path))
    launcher = _StaticLauncher(["127.0.0.1:1"])
    engine = _FakeEngine()
    engine.alerts = [_alert("shed_storm")]
    ctl = FleetController(
        _FakeCollector([_row("h0:1"), _row("h1:1")]), engine,
        launcher=launcher, min_replicas=2, max_replicas=4,
        hysteresis=1, dry_run=True, cooldown_s=_ZERO_COOLDOWNS)
    recs = ctl.tick()
    assert [r["outcome"] for r in recs] == ["skipped"]
    assert recs[0]["reason"] == "dry_run"
    assert recs[0]["alert_id"] == engine.alerts[0]["id"]
    assert launcher.launched == []  # intent only, no actuation
    assert ctl.status()["mode"] == "dry_run"
    names = [n for n, _ in _actions(str(tmp_path))]
    assert names == ["requested", "skipped"]
    # dry-run still honors the cooldown: the next tick inside the
    # window journals nothing new
    ctl.cooldown_s["scale_out"] = 3600.0
    assert ctl.tick() == []


def test_scale_out_hysteresis_lifecycle_and_double_act_guard(tmp_path):
    events_lib.configure(str(tmp_path))
    httpd, addr = _healthz_server()
    try:
        launcher = _StaticLauncher([addr])
        engine = _FakeEngine()
        engine.alerts = [_alert("ttft_regression")]
        ctl = FleetController(
            _FakeCollector([_row("h0:1"), _row("h1:1")]), engine,
            launcher=launcher, min_replicas=2, max_replicas=3,
            hysteresis=2, cooldown_s=_ZERO_COOLDOWNS, verify_s=5.0)
        assert ctl.tick() == []  # streak 1 < hysteresis: one spike
        recs = ctl.tick()       # streak 2: act
        assert [r["outcome"] for r in recs] == ["effective"]
        rec = recs[0]
        assert rec["action"] == "scale_out" and rec["addr"] == addr
        assert rec["id"].startswith("act-scale_out-")
        assert rec["trigger"] == "ttft_regression"
        assert rec["alert_id"] == engine.alerts[0]["id"]
        # launched-but-undiscovered counts into fleet size: the still-
        # firing alert must not double-launch inside discovery latency
        assert ctl.tick() == []
        assert launcher.launched == [addr]
        names = [n for n, _ in _actions(str(tmp_path))]
        assert names == ["requested", "acting", "effective"]
        assert get_registry().get_value(
            "controller_actions_total",
            {"action": "scale_out", "outcome": "effective"}) == 1.0
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_scale_out_rolls_back_unverifiable_launch(tmp_path):
    events_lib.configure(str(tmp_path))
    launcher = _StaticLauncher(["127.0.0.1:1"])  # nothing listens there
    engine = _FakeEngine()
    engine.alerts = [_alert("shed_storm")]
    ctl = FleetController(
        _FakeCollector([_row("h0:1"), _row("h1:1")]), engine,
        launcher=launcher, min_replicas=2, max_replicas=3,
        hysteresis=1, cooldown_s=_ZERO_COOLDOWNS, verify_s=0.3)
    recs = ctl.tick()
    assert [r["outcome"] for r in recs] == ["rolled_back"]
    assert launcher.stopped == ["127.0.0.1:1"]  # the reversal
    names = [n for n, _ in _actions(str(tmp_path))]
    assert names == ["requested", "acting", "rolled_back"]


def test_budget_zero_latches_degraded_and_reset_rearms(tmp_path):
    events_lib.configure(str(tmp_path))
    launcher = _StaticLauncher(["127.0.0.1:1"])
    engine = _FakeEngine()
    engine.alerts = [_alert("shed_storm")]
    ctl = FleetController(
        _FakeCollector([_row("h0:1"), _row("h1:1")]), engine,
        launcher=launcher, min_replicas=2, max_replicas=4,
        hysteresis=1, cooldown_s=_ZERO_COOLDOWNS,
        budget_max_actions=0, budget_window_s=60.0)
    recs = ctl.tick()
    assert [r["outcome"] for r in recs] == ["skipped"]
    assert recs[0]["reason"] == "budget_exhausted"
    assert ctl.mode == "degraded (budget_exhausted)"
    assert launcher.launched == []  # observe-only: nothing acted
    assert get_registry().get_value("controller_mode") == 2.0
    modes = [d for n, d in _actions(str(tmp_path)) if n == "mode"]
    assert modes and modes[0]["mode"] == "degraded (budget_exhausted)"
    # operator re-arm: journaled, gauged, mode back to active
    ctl.reset_budget()
    assert ctl.mode == "active"
    assert get_registry().get_value("controller_mode") == 0.0
    modes = [d for n, d in _actions(str(tmp_path)) if n == "mode"]
    assert modes[-1] == {"mode": "active", "reason": "budget_reset"}


def test_scale_in_picks_least_loaded_and_never_redrains(tmp_path):
    events_lib.configure(str(tmp_path))
    rows = [_row("h0:1", queue_depth=5), _row("h1:1", queue_depth=0),
            _row("h2:1", queue_depth=2)]
    ctl = _DrainRecorder(
        _FakeCollector(rows), _FakeEngine(), launcher=None,
        min_replicas=2, max_replicas=4, calm_ticks=2,
        cooldown_s=_ZERO_COOLDOWNS)
    assert ctl.tick() == []  # calm streak 1 < calm_ticks
    recs = ctl.tick()
    assert [r["outcome"] for r in recs] == ["effective"]
    assert recs[0]["action"] == "scale_in"
    assert recs[0]["trigger"] == "calm"
    assert ctl.drains == ["h1:1"]  # the least-loaded replica
    # the collector still reports the victim "ok" inside its staleness
    # window; the drained-guard excludes it, so the fleet reads 2 ==
    # min_replicas and nothing else is drained
    assert ctl.tick() == []
    assert ctl.drains == ["h1:1"]


def test_rebalance_pushes_weights_only_on_material_change(tmp_path):
    events_lib.configure(str(tmp_path))
    pushed = []
    rows = [_row("h0:1", queue_depth=0),
            _row("h1:1", queue_depth=3, admission="shedding")]
    ctl = FleetController(
        _FakeCollector(rows), _FakeEngine(), weights_sink=pushed.append,
        min_replicas=2, max_replicas=4, cooldown_s=_ZERO_COOLDOWNS)
    recs = ctl.tick()
    assert [r["action"] for r in recs] == ["rebalance"]
    assert len(pushed) == 1
    # inverse queue depth, shedding quartered, best replica = 1.0
    assert pushed[0]["h0:1"] == 1.0
    assert abs(pushed[0]["h1:1"] - (0.25 / 4) / 1.0) < 1e-9
    assert ctl.tick() == []  # unchanged weights: no second push
    assert len(pushed) == 1


# --------------------------- satellite: scale-in under live load

def _make_replica(port=0, *, slots=4, step_delay_s=0.004,
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


def test_controller_scale_in_under_load_zero_failed(tmp_path):
    """The ISSUE-17 satellite: a controller-initiated drain while a
    live request stream runs — zero failed requests (router failover
    absorbs the drain), session pinning respected throughout, and the
    victim's slots verifiably reclaimed. Extends the PR-7 rolling-
    restart drill to controller-initiated drains."""
    events_lib.configure(str(tmp_path))
    boxes = [_make_replica(), _make_replica()]
    stop = threading.Event()

    def undertaker():
        # when the drain stops a service, close its socket so the
        # controller's healthz poll sees the replica actually die
        while not stop.is_set():
            for b in boxes:
                if b["svc"]._stop:
                    try:
                        b["httpd"].server_close()
                    except OSError:
                        pass
            time.sleep(0.05)

    threading.Thread(target=undertaker, daemon=True).start()
    rs = ReplicaSet(tuple(b["addr"] for b in boxes))
    prober = HealthProber(rs, interval_s=0.15)
    prober.probe_once()
    prober.start()
    router = Router(rs, timeout_s=30.0)

    # pin a session first, then make the controller drain the OTHER
    # replica (fake load rows steer the least-loaded victim choice)
    raw, body = (json.dumps({"prompt": "turn one", "max_tokens": 4,
                             "keep": True}).encode(),
                 {"prompt": "turn one", "max_tokens": 4, "keep": True})
    status, rbody = router.request("/v1/completions", raw, body)
    assert status == 200
    sid = json.loads(rbody)["session"]
    owner = router.sessions[sid]
    victim = next(b for b in boxes if b["addr"] != owner)

    statuses, session_statuses = [], []
    lock = threading.Lock()

    def traffic():
        i = 0
        while not stop.is_set():
            b = {"prompt": f"bg {i}", "max_tokens": 3}
            s, _ = router.request("/v1/completions",
                                  json.dumps(b).encode(), b)
            with lock:
                statuses.append(s)
            i += 1
            time.sleep(0.02)

    def session_traffic():
        # each kept resume consumes the session and parks a NEW one
        # (a linear chain) — the client follows the returned id, and
        # the pin must keep every link on the owning replica
        cur = sid
        i = 0
        while not stop.is_set():
            b = {"prompt": f"turn {i}", "max_tokens": 3,
                 "session": cur, "keep": True}
            s, rb = router.request("/v1/completions",
                                   json.dumps(b).encode(), b)
            if s == 200:
                cur = json.loads(rb)["session"]
            with lock:
                session_statuses.append((s, router.sessions.get(cur)))
            i += 1
            time.sleep(0.05)

    threads = [threading.Thread(target=traffic, daemon=True),
               threading.Thread(target=session_traffic, daemon=True)]
    for t in threads:
        t.start()
    rows = [_row(owner, queue_depth=2),
            _row(victim["addr"], queue_depth=0)]
    ctl = FleetController(
        _FakeCollector(rows), _FakeEngine(), launcher=None,
        min_replicas=1, max_replicas=2, calm_ticks=1,
        cooldown_s=_ZERO_COOLDOWNS, drain_timeout_s=20.0,
        http_timeout_s=2.0)
    try:
        time.sleep(0.4)  # traffic in flight before the act
        recs = ctl.tick()  # the controller-initiated drain, real HTTP
        assert [(r["action"], r["outcome"]) for r in recs] == [
            ("scale_in", "effective")], recs
        assert recs[0]["addr"] == victim["addr"]
        time.sleep(0.8)  # post-drain traffic rides the survivor
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        prober.stop()
    assert statuses and all(s == 200 for s in statuses), (
        [s for s in statuses if s != 200][:5], len(statuses))
    # session pinning respected: every turn answered 200 by its owner
    assert session_statuses
    assert all(s == 200 for s, _ in session_statuses)
    assert all(a == owner for _, a in session_statuses)
    # the victim's slots are verifiably reclaimed: drained service
    # stopped with nothing queued and no slot held
    assert victim["svc"]._stop
    acct = victim["batcher"].slot_accounting()
    assert acct["active"] == 0 and acct["free"] == acct["slots"], acct
    assert not victim["batcher"].queue
    # journaled as a controller action, cross-linked trigger "calm"
    acts = [d for n, d in _actions(str(tmp_path)) if n == "effective"]
    assert acts and acts[-1]["action"] == "scale_in"
    assert acts[-1]["trigger"] == "calm"
    for b in boxes:
        if not b["svc"]._stop:
            _kill_replica(b)
