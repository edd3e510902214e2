"""Store resilience plane (store_plane.py — ISSUE 18): bounded ops
that abandon a wedged transport, retry-with-reconnect, the key-absent
"answer, not outage" contract, the ok→degraded→down health machine and
its metrics/journal arc, `for=` fault windows, the last-known-good
discovery cache riding out a blackout, the partial-publish hole pin in
discovery, liveness blame suspension under store flaps (vs the
all-stale signature), the store_degraded alert + fleet_stale hold, the
controller's observe-only store latch, and the offline console /
report / timeline surfaces. The end-to-end blackout drills (training
gang + serving router, tools/store_outage_drill.py) ride along as slow
tests."""

import json
import os
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import fleet_console  # noqa: E402
import obs_report  # noqa: E402
import timeline_report  # noqa: E402

from pytorch_distributed_train_tpu import elastic, store_plane  # noqa: E402
from pytorch_distributed_train_tpu.faults import (  # noqa: E402
    registry as fregistry,
)
from pytorch_distributed_train_tpu.faults.registry import (  # noqa: E402
    InjectedFault,
)
from pytorch_distributed_train_tpu.faults.retry import (  # noqa: E402
    RetryPolicy,
)
from pytorch_distributed_train_tpu.fleet.controller import (  # noqa: E402
    FleetController,
    ReplicaLauncher,
)
from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.alerts import AlertEngine  # noqa: E402
from pytorch_distributed_train_tpu.obs.collector import (  # noqa: E402
    FleetCollector,
)
from pytorch_distributed_train_tpu.obs.events import load_events  # noqa: E402
from pytorch_distributed_train_tpu.obs.registry import get_registry  # noqa: E402
from pytorch_distributed_train_tpu.sentinel.liveness import (  # noqa: E402
    LivenessPlane,
)


@pytest.fixture(autouse=True)
def _clean_planes():
    fregistry._reset_for_tests()
    store_plane._reset_for_tests()
    yield
    fregistry._reset_for_tests()
    store_plane._reset_for_tests()
    events_lib._reset_for_tests()


# ------------------------------------------------------------- fakes

class _FakeKV:
    """Dict-backed launcher-store stand-in (native/store.py surface):
    get raises key-absent TimeoutError, add is the int64 counter."""

    def __init__(self, data=None):
        self.data = {} if data is None else data
        self.calls = {"get": 0, "set": 0, "add": 0}

    def set(self, key, value):
        self.calls["set"] += 1
        self.data[key] = value

    def get(self, key, timeout_ms=0):
        self.calls["get"] += 1
        if key not in self.data:
            raise TimeoutError(key)
        return self.data[key]

    def add(self, key, delta):
        self.calls["add"] += 1
        v = int(self.data.get(key, 0)) + int(delta)
        self.data[key] = v
        return v

    def close(self):
        pass


class _FlakyStore(_FakeKV):
    """_FakeKV whose transport can be switched off (``broken`` is a
    one-element list so tests flip it mid-flight)."""

    def __init__(self, data, broken):
        super().__init__(data)
        self.broken = broken

    def set(self, key, value):
        if self.broken[0]:
            raise ConnectionError("store blackout")
        super().set(key, value)

    def get(self, key, timeout_ms=0):
        if self.broken[0]:
            raise ConnectionError("store blackout")
        return super().get(key, timeout_ms=timeout_ms)


def _fast_policy(attempts):
    return RetryPolicy(max_attempts=attempts, base_delay_s=0.01,
                       max_delay_s=0.02, jitter=0.0)


def _hb(step):
    return json.dumps({"step": step, "ts": time.time()}).encode()


# ----------------------------------------------- ResilientStore units

def test_bounded_op_abandons_wedged_transport():
    """A wedged TCP send must never wedge the caller: the op comes
    back as StoreOpTimeout at the deadline, scored as a health
    failure, while the stuck worker is abandoned (not joined)."""
    release = threading.Event()

    class _Wedged:
        def set(self, key, value):
            release.wait(10.0)  # far past any deadline

        def close(self):
            pass

    health = store_plane.StoreHealth()
    rs = store_plane.ResilientStore(
        lambda: _Wedged(), op_timeout_s=0.2, policy=_fast_policy(1),
        health=health, name="t")
    try:
        t0 = time.monotonic()
        with pytest.raises(store_plane.StoreOpTimeout):
            rs.set("k", b"v")
        assert time.monotonic() - t0 < 5.0  # bounded, not the full wait
        snap = health.snapshot()
        assert snap["failures_total"] == 1
        assert snap["ops_total"] == 1
    finally:
        release.set()
        rs.close()


def test_retry_reconnects_through_transient_transport_error():
    data = {}
    made = []

    class _Flaky:
        def __init__(self, fail):
            self.fail = fail

        def set(self, key, value):
            if self.fail:
                raise ConnectionError("transport reset")
            data[key] = value

        def close(self):
            pass

    def factory():
        made.append(1)
        return _Flaky(fail=len(made) == 1)  # only the first client bad

    health = store_plane.StoreHealth()
    rs = store_plane.ResilientStore(
        factory, op_timeout_s=1.0, policy=_fast_policy(3),
        health=health, name="t")
    try:
        rs.set("k", b"v")
    finally:
        rs.close()
    assert data == {"k": b"v"}
    assert len(made) == 2  # the poisoned client was replaced, not reused
    snap = health.snapshot()
    assert snap["failures_total"] == 1
    assert snap["state"] == "ok"  # one success snaps health back


def test_key_absent_is_an_answer_not_an_outage():
    kv = _FakeKV()
    health = store_plane.StoreHealth()
    rs = store_plane.ResilientStore(
        lambda: kv, op_timeout_s=1.0, policy=_fast_policy(3),
        health=health, name="t")
    try:
        with pytest.raises(TimeoutError) as ei:
            rs.get("never/published", timeout_ms=10)
        assert not isinstance(ei.value, store_plane.StoreOpTimeout)
        assert kv.calls["get"] == 1  # an answer is not retried
        snap = health.snapshot()
        assert snap["failures_total"] == 0
        assert snap["state"] == "ok"
    finally:
        rs.close()


def test_health_machine_transitions_and_metrics():
    clk = [0.0]
    before = get_registry().get_value("store_degraded_total") or 0.0
    h = store_plane.StoreHealth(degraded_after=2, down_after_s=5.0,
                                clock=lambda: clk[0])
    h.record_failure("get", OSError("x"))
    assert h.snapshot()["state"] == "ok"  # one blip is not degradation
    h.record_failure("get", OSError("x"))
    assert h.snapshot()["state"] == "degraded"
    assert get_registry().get_value("store_health_state") == 1.0
    clk[0] += 6.0  # failures persisted past down_after_s
    h.record_failure("get", OSError("x"))
    assert h.snapshot()["state"] == "down"
    assert get_registry().get_value("store_health_state") == 2.0
    h.record_success("get", 0.01)
    snap = h.snapshot()
    assert snap["state"] == "ok"
    assert snap["ops_total"] == 4 and snap["failures_total"] == 3
    assert snap["consecutive_failures"] == 0
    assert "OSError" in (snap["last_error"] or "")
    # the counter scores INCIDENTS (ok-exits), not every sub-transition
    after = get_registry().get_value("store_degraded_total") or 0.0
    assert after == before + 1


def test_for_window_fault_fires_then_exhausts():
    fregistry.configure(("store.get@call=1:for=0.3:gen=-1",))
    with pytest.raises(InjectedFault):
        fregistry.maybe_fire("store.get")
    with pytest.raises(InjectedFault):  # EVERY traversal inside the window
        fregistry.maybe_fire("store.get")
    time.sleep(0.35)
    assert not fregistry.maybe_fire("store.get")  # window exhausted
    assert not fregistry.maybe_fire("store.get")


def test_lkg_cache_serves_discovery_through_blackout(tmp_path):
    events_lib.configure(str(tmp_path / "events"))
    kv = _FakeKV()
    rs = store_plane.ResilientStore(
        lambda: kv, op_timeout_s=1.0, policy=_fast_policy(2), name="t")
    addrs = ["127.0.0.1:1111", "127.0.0.1:2222"]
    try:
        for a in addrs:
            elastic.publish_replica(rs, a)
        assert rs.discover_replicas() == addrs  # primes the LKG cache
        before = get_registry().get_value(
            "store_lkg_reads_total", {"registry": "replicas"}) or 0.0
        fregistry.configure(("store.add@call=1:count=1000:gen=-1",
                             "store.get@call=1:count=1000:gen=-1"))
        assert rs.discover_replicas() == addrs  # served from cache
        assert get_registry().get_value(
            "store_lkg_reads_total", {"registry": "replicas"}) == before + 1
        assert store_plane.health_snapshot()["state"] in ("degraded",
                                                          "down")
        fregistry.configure(())  # blackout ends
        assert rs.discover_replicas() == addrs  # live read again
        snap = store_plane.health_snapshot()
        assert snap["state"] == "ok"
        assert snap["lkg_serves"]  # the serve was accounted
    finally:
        rs.close()
    events_lib._reset_for_tests()  # flush + close the journal
    names = [e["name"] for e in load_events(str(tmp_path / "events"))
             if e["category"] == "store"]
    assert "degraded" in names or "down" in names
    assert "recovered" in names


def test_discovery_skips_partial_publish_hole():
    """A publisher that crashed between add(COUNT) and set(key) leaves
    a counter-covered hole: skippable forever, under strict too — the
    key-absent TimeoutError is an ANSWER from a healthy store."""
    kv = _FakeKV()
    for a in ("a:1", "b:2", "c:3"):
        elastic.publish_replica(kv, a)
    del kv.data[f"{elastic.SERVE_REPLICA_KEY_PREFIX}1"]
    assert elastic.discover_replicas(kv) == ["a:1", "c:3"]
    assert elastic.discover_replicas(kv, strict=True) == ["a:1", "c:3"]
    for a in ("a:1", "b:2", "c:3"):
        elastic.publish_obs_endpoint(kv, "serving", a, host=a)
    del kv.data[f"{elastic.OBS_ENDPOINT_KEY_PREFIX}1"]
    recs = elastic.discover_obs_endpoints(kv, strict=True)
    assert [r["idx"] for r in recs] == [0, 2]
    assert [r["addr"] for r in recs] == ["a:1", "c:3"]


# ------------------------------------------- liveness under store flaps

def test_liveness_suspends_blame_through_store_flap(tmp_path):
    """A store blackout longer than hang_timeout_s makes every host
    look stale at once — the monitor must suspend blame (no exit, no
    diagnosis), count the dropped beats, and re-arm on recovery."""
    events_lib.configure(str(tmp_path))
    data, broken = {}, [False]
    exits = []
    before = get_registry().get_value(
        "store_beats_dropped_total", {"reason": "error"}) or 0.0
    plane = LivenessPlane(
        hang_timeout_s=0.5, poll_s=0.1, exit_code=43,
        store_factory=lambda: _FlakyStore(data, broken),
        rank=0, world=2, gen="0", exit_fn=exits.append,
        store_health=store_plane.StoreHealth())
    assert plane.start()
    try:
        step, t0 = 0, time.time()
        while time.time() - t0 < 0.4:  # both hosts beating, store fine
            step += 1
            plane.beat(step)
            data["sentinel/0/hb/1"] = _hb(step)
            time.sleep(0.05)
        broken[0] = True  # blackout, longer than hang_timeout_s
        t0 = time.time()
        while time.time() - t0 < 1.2:
            step += 1
            plane.beat(step)  # drops (counted), never blocks the step
            time.sleep(0.05)
        assert exits == [] and plane.blamed is None
        assert plane.suspended  # the outage signature was recognized
        broken[0] = False  # heal: beats resume on both hosts
        deadline = time.time() + 8.0
        while plane.suspended and time.time() < deadline:
            step += 1
            plane.beat(step)
            data["sentinel/0/hb/1"] = _hb(step)
            time.sleep(0.05)
        assert not plane.suspended
        assert exits == [] and plane.blamed is None
    finally:
        plane.stop()
    after = get_registry().get_value(
        "store_beats_dropped_total", {"reason": "error"}) or 0.0
    assert after > before
    events_lib._reset_for_tests()
    names = [e["name"] for e in load_events(str(tmp_path))
             if e["category"] == "store"]
    assert "blame_suspended" in names and "blame_resumed" in names


def test_liveness_outage_never_blames_unseen_peer():
    """Rank 1 never heartbeat (still compiling) when the store blacked
    out: a host that never started is not blamable, before, during or
    after the outage."""
    data, broken = {}, [False]
    exits = []
    plane = LivenessPlane(
        hang_timeout_s=0.4, poll_s=0.1, exit_code=43,
        store_factory=lambda: _FlakyStore(data, broken),
        rank=0, world=2, gen="0", exit_fn=exits.append,
        store_health=store_plane.StoreHealth())
    assert plane.start()
    try:
        t0 = time.time()
        while time.time() - t0 < 0.3:
            plane.beat(1)
            time.sleep(0.05)
        broken[0] = True
        time.sleep(0.9)
        broken[0] = False
        t0 = time.time()
        while time.time() - t0 < 0.6:
            plane.beat(2)
            time.sleep(0.05)
        assert exits == [] and plane.blamed is None
    finally:
        plane.stop()


def test_liveness_all_stale_with_healthy_store_suspends(tmp_path):
    """EVERY host going silent at once while the store answers fine is
    still a control-plane signature (network partition, launcher GC
    pause) — suspend, don't pick a victim."""
    events_lib.configure(str(tmp_path))
    data = {}
    exits = []
    plane = LivenessPlane(
        hang_timeout_s=0.4, poll_s=0.1, exit_code=43,
        store_factory=lambda: _FlakyStore(data, [False]),
        rank=0, world=2, gen="0", exit_fn=exits.append,
        store_health=store_plane.StoreHealth())
    assert plane.start()
    try:
        plane.beat(1)
        data["sentinel/0/hb/1"] = _hb(1)
        deadline = time.time() + 5.0  # then: silence on BOTH hosts
        while not plane.suspended and time.time() < deadline:
            time.sleep(0.05)
        assert plane.suspended
        assert exits == [] and plane.blamed is None
        step, deadline = 1, time.time() + 8.0
        while plane.suspended and time.time() < deadline:
            step += 1
            plane.beat(step)
            data["sentinel/0/hb/1"] = _hb(step)
            time.sleep(0.05)
        assert not plane.suspended and exits == []
    finally:
        plane.stop()
    events_lib._reset_for_tests()
    sus = [e for e in load_events(str(tmp_path))
           if e["category"] == "store" and e["name"] == "blame_suspended"]
    assert sus and sus[0]["detail"]["reason"] == "all_stale"


# --------------------------------------------- alert engine + controller

def test_store_degraded_alert_fires_resolves_and_holds_fleet_stale(
        tmp_path):
    events_lib.configure(str(tmp_path))
    alive = {"up": True}

    def fetch(url, timeout_s):
        if not alive["up"]:
            raise OSError("connection refused")
        return 200, (b"train_step 1\n" if url.endswith("/metrics")
                     else b"{}")

    col = FleetCollector(
        store_factory=lambda: None,
        endpoints=[{"role": "serving", "host": "hostA",
                    "addr": "127.0.0.1:9999"}],
        poll_s=0.05, stale_after_s=0.2, fetch=fetch)
    engine = AlertEngine()
    col.poll()
    engine.evaluate(col)  # hostA scraped ok once
    h = store_plane.get_health()
    h.record_failure("get", OSError("blackout"))
    h.record_failure("get", OSError("blackout"))  # → degraded
    alive["up"] = False
    time.sleep(0.3)  # hostA goes stale DURING the outage
    col.poll()
    transitions = engine.evaluate(col)
    fired = [(r["rule"], r["host"]) for r in transitions
             if r["event"] == "fired"]
    assert ("store_degraded", "launcher") in fired
    # staleness evidence is untrustworthy while the store is out:
    # fleet_stale is HELD, neither firing nor resolving
    assert not any(r == "fleet_stale" for r, _h in fired)
    assert any(f["rule"] == "store_degraded" for f in engine.firing())
    h.record_success("get", 0.01)  # store recovers; hostA still stale
    transitions = engine.evaluate(col)
    assert any(r["event"] == "resolved" and r["rule"] == "store_degraded"
               for r in transitions)
    fired = [(r["rule"], r["host"]) for r in transitions
             if r["event"] == "fired"]
    assert ("fleet_stale", "hostA") in fired  # evidence trusted again


def test_controller_latches_observe_only_during_store_outage(tmp_path):
    events_lib.configure(str(tmp_path))

    class _Col:
        def __init__(self):
            self.snap = {"state": "degraded", "ops_total": 3}

        def serving_rows(self):
            return [{"addr": a, "host": a.split(":")[0], "state": "ok",
                     "role": "serving", "queue_depth": 0,
                     "admission": "ok", "shed_per_s": 0.0}
                    for a in ("h0:1", "h1:1")]

        def store_health(self):
            return dict(self.snap)

    class _Engine:
        def __init__(self):
            self.alerts = [{"rule": "shed_storm", "role": "serving",
                            "host": "h0", "for_s": 2.0, "value": 5.0,
                            "baseline": 0.0, "id": "shed_storm@h0@1"}]

        def subscribe(self, fn):
            pass

        def firing(self):
            return [dict(a) for a in self.alerts]

    class _Launcher(ReplicaLauncher):
        def __init__(self):
            self.launched = []

        def launch(self):
            self.launched.append("x:1")
            return "x:1"

        def stop(self, addr):
            return True

    col, launcher = _Col(), _Launcher()
    ctl = FleetController(
        col, _Engine(), launcher=launcher, min_replicas=2,
        max_replicas=4, hysteresis=1,
        cooldown_s={"scale_out": 0.0, "scale_in": 0.0, "recycle": 0.0,
                    "rebalance": 0.0})
    recs = ctl.tick()
    assert ctl.status()["mode"] == "degraded (store)"
    assert [r["outcome"] for r in recs] == ["skipped"]
    assert recs[0]["reason"] == "store_degraded"
    assert launcher.launched == []  # observe-only: journaled, not acted
    col.snap = {"state": "ok", "ops_total": 5}  # store recovers
    ctl.tick()
    assert ctl.status()["mode"] == "active"  # the hold clears itself


# ------------------------------------------------- offline surfaces

def test_offline_surfaces_render_store_arc(tmp_path):
    events_dir = str(tmp_path / "events")
    events_lib.configure(events_dir, who="fleet")
    events_lib.emit("store", "degraded", prev="ok", op="get",
                    error="ConnectionError: x", consecutive=2)
    events_lib.emit("store", "blame_suspended", reason="store_degraded")
    events_lib.emit("store", "blame_resumed")
    events_lib.emit("store", "recovered", prev="degraded")
    events_lib._reset_for_tests()  # flush + close the journal
    out = fleet_console.offline_report(str(tmp_path),
                                       events_dir=events_dir)
    assert "store: ok at end" in out
    assert "degraded-transitions=1" in out
    assert "blame-suspensions=1" in out
    lines = obs_report.store_section(events_dir)
    assert lines and "store health" in lines[0]
    assert "degraded=1" in lines[0] and "recovered=1" in lines[0]
    events = load_events(events_dir)
    text = "\n".join(timeline_report.timeline_lines(events, width=60))
    assert "STORE" in text
    assert "degraded" in text and "recovered" in text
    for pair in (("store", "degraded"), ("store", "recovered"),
                 ("store", "blame_suspended")):
        assert pair in timeline_report._LANDMARKS


# --------------------------------------------------- e2e drills (slow)

@pytest.mark.slow
def test_store_outage_training_drill(tmp_path):
    import store_outage_drill

    rep = store_outage_drill.run_training_drill(
        seed=0, steps=18, outage_s=3.0, out_dir=str(tmp_path))
    assert rep["ok"], rep
    assert rep["false_hang_blames"] == 0
    assert rep["store_degraded"] and rep["store_recovered"]
    assert rep["blame_suspended"] and rep["blame_resumed"]
    assert rep["cadence_ok"]


@pytest.mark.slow
def test_store_outage_serving_drill(tmp_path):
    import store_outage_drill

    rep = store_outage_drill.run_serving_drill(
        outage_s=2.0, requests=12, out_dir=str(tmp_path))
    assert rep["ok"], rep
    assert rep["requests_failed"] == 0
    assert rep["state_after"] == "ok"
