"""Serving reliability plane (serving_plane/ + tools/serve_http.py):
admission control, deadlines + 504 slot reclaim, the abandoned-stream
slot-leak fix and its `serve.slot_leak` drill, tail-latency anomalies
firing the (fake) managed profiler, the /healthz reliability surface,
and the seeded SLO soak smoke."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import serve_http  # noqa: E402

from pytorch_distributed_train_tpu.faults import (  # noqa: E402
    registry as fregistry,
)
from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.goodput import (  # noqa: E402
    SERVE_BUCKETS,
    GoodputTracker,
)
from pytorch_distributed_train_tpu.obs.registry import get_registry  # noqa: E402
from pytorch_distributed_train_tpu.serving_plane import (  # noqa: E402
    AdmissionController,
    DeadlineExceeded,
    OverloadShed,
    ReliabilityPlane,
    SloTracker,
    TailLatencyMonitor,
)
from pytorch_distributed_train_tpu.serving_plane.testing import (  # noqa: E402
    FakeByteTok,
    FakeCaptureBackend,
    FakeTokenBatcher,
)


@pytest.fixture(autouse=True)
def _clean_planes():
    fregistry._reset_for_tests()
    yield
    fregistry._reset_for_tests()
    events_lib._reset_for_tests()


def _service(*, slots=2, step_delay_s=0.01, orphan_grace_s=0.3, **plane_kw):
    plane = ReliabilityPlane(slots=slots, **plane_kw)
    batcher = FakeTokenBatcher(slots=slots, step_delay_s=step_delay_s)
    svc = serve_http.BatcherService(batcher, FakeByteTok(), plane=plane,
                                    orphan_grace_s=orphan_grace_s)
    return svc, batcher


def _counter(name):
    return get_registry().get_value(name) or 0.0


# --------------------------------------------------------------- units

def test_admission_controller_units():
    a = AdmissionController(max_queue_depth=4, shed_ttft_s=2.0)
    assert a.enabled
    assert a.check(0, 0.1) is None
    assert a.state(0, 0.1) == "ok"
    # depth shed: retry-after integral, >= 1, <= cap
    ra = a.check(4, 0.0)
    assert ra is not None and 1.0 <= ra <= 30.0 and ra == int(ra)
    # latency shed: hint follows the estimate
    ra = a.check(1, 7.3)
    assert ra == 8.0
    assert a.state(1, 7.3) == "shedding"
    # both knobs off = never shed
    off = AdmissionController()
    assert not off.enabled and off.check(10 ** 6, 10 ** 6) is None
    with pytest.raises(ValueError):
        AdmissionController(max_queue_depth=-1)


def test_slo_tracker_lifecycle_and_deadlines():
    t = SloTracker(window=16)
    t.on_submit(1, deadline_ts=100.0, now=0.0)
    t.on_submit(2, deadline_ts=None, now=1.0)
    assert t.expired(now=50.0) == []
    assert t.expired(now=101.0) == [1]
    assert t.oldest_inflight() == 1
    # first tokens: TTFT + implicit queue-wait sample
    ttft = t.on_tokens(1, 1, now=2.5)
    assert ttft == pytest.approx(2.5)
    assert t.on_tokens(1, 2, now=3.5) is None  # inter-token now
    t.on_finish(1, "ok", now=4.0)
    t.on_finish(2, "deadline", now=4.0)
    snap = t.snapshot()
    assert snap["inflight"] == 0
    assert snap["outcomes"] == {"ok": 1, "deadline": 1}
    assert snap["ttft_s"]["p50"] == pytest.approx(2.5)
    assert snap["inter_token_s"]["p50"] == pytest.approx(0.5)
    # est TTFT monotone in queue depth
    assert t.est_ttft_s(8, 2) > t.est_ttft_s(0, 2)


def test_goodput_serving_vocabulary():
    g = GoodputTracker(t0=0.0, buckets=SERVE_BUCKETS,
                       productive=("prefill", "decode"))
    g.account("prefill", 1.0)
    g.account("decode", 3.0)
    g.account("stalled", 1.0)
    snap = g.snapshot(now=10.0)
    assert snap["goodput_s_prefill"] == 1.0
    assert snap["goodput_s_stalled"] == 1.0
    assert snap["goodput_s_idle"] == pytest.approx(5.0)
    assert snap["goodput_pct"] == pytest.approx(40.0)
    # train vocabulary unchanged by the extension
    t = GoodputTracker(t0=0.0)
    t.account("step", 5.0)
    assert t.snapshot(now=10.0)["goodput_pct"] == pytest.approx(50.0)


def test_tail_monitor_journals_and_fires_fake_profiler(tmp_path):
    from pytorch_distributed_train_tpu.config import ObsConfig
    from pytorch_distributed_train_tpu.obs.events import load_events
    from pytorch_distributed_train_tpu.obs.profiler import ManagedProfiler

    events_lib.configure(str(tmp_path / "events"))
    backend = FakeCaptureBackend()
    prof = ManagedProfiler(ObsConfig(profile_dir=str(tmp_path / "prof")),
                           run_dir=str(tmp_path), backend=backend)
    mon = TailLatencyMonitor(min_samples=8, profiler=prof,
                             capture_seconds=0.05, cooldown_s=60.0)
    for _ in range(10):
        assert not mon.observe_ttft(0.01)
    assert mon.observe_ttft(5.0)  # a 500x spike
    time.sleep(0.3)  # let the ad-hoc capture's stop timer run
    assert len(backend.dirs) == 1
    assert os.path.exists(os.path.join(backend.dirs[0], "FAKE_CAPTURE"))
    # second spike inside the cooldown: journaled, NOT captured
    for _ in range(10):
        mon.observe_ttft(0.01)
    assert mon.observe_ttft(5.0)
    assert len(backend.dirs) == 1
    evs = load_events(str(tmp_path / "events"))
    kinds = [(e["category"], e["name"]) for e in evs]
    assert ("serve", "tail_latency") in kinds
    assert ("anomaly", "ttft_regression") in kinds
    assert ("profile", "capture_start") in kinds
    assert ("profile", "capture_end") in kinds


# ------------------------------------------------------ deadlines (504)

def test_deadline_expiry_cancels_and_reclaims_slot():
    svc, batcher = _service(slots=2, step_delay_s=0.02)
    before = _counter("serve_deadline_expired_total")
    try:
        with pytest.raises(DeadlineExceeded):
            svc.complete("long request", 10_000, 0.0, timeout_s=30.0,
                         deadline_s=0.15)
        # the 504'd request's KV slot is verifiably reclaimed
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            acct = batcher.slot_accounting()
            if acct["active"] == 0 and acct["queued"] == 0:
                break
            time.sleep(0.01)
        assert acct["active"] == 0 and acct["queued"] == 0
        assert _counter("serve_deadline_expired_total") == before + 1
        assert svc.plane.slo.snapshot()["outcomes"].get("deadline") == 1
    finally:
        svc.shutdown()


def test_deadline_default_applies_and_stream_expires():
    svc, batcher = _service(slots=1, step_delay_s=0.02,
                            deadline_default_s=0.15)
    try:
        # non-streamed: server default budget, no per-request field
        with pytest.raises(DeadlineExceeded):
            svc.complete("x", 10_000, 0.0, timeout_s=30.0)
        # streamed: the chunk iterator surfaces the expiry
        _, _, chunks = svc.stream("y", 10_000, 0.0, timeout_s=30.0)
        with pytest.raises(DeadlineExceeded):
            for _toks, c in chunks:
                if c is not None:
                    break
        assert batcher.slot_accounting()["active"] == 0
    finally:
        svc.shutdown()


def test_serve_deadline_fault_point_forces_504():
    """serve.deadline drill: no deadline anywhere, yet the request is
    force-expired deterministically — 504 + slot reclaim."""
    fregistry.configure(specs=("serve.deadline@call=1",))
    svc, batcher = _service(slots=1, step_delay_s=0.02)
    try:
        with pytest.raises(DeadlineExceeded):
            svc.complete("victim", 10_000, 0.0, timeout_s=30.0)
        assert batcher.slot_accounting()["active"] == 0
    finally:
        svc.shutdown()


# --------------------------------------------------------- admission

def test_admission_sheds_with_retry_after_over_http():
    svc, _ = _service(slots=1, step_delay_s=0.05, max_queue_depth=1)
    from http.server import ThreadingHTTPServer

    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                serve_http.make_handler(svc))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    try:
        # occupy the only slot, then the queue's one allowed spot
        t1 = threading.Thread(target=lambda: _swallow(
            svc, "slotholder", 40))
        t1.start()
        deadline = time.monotonic() + 5.0
        while (time.monotonic() < deadline
               and not svc.batcher.active_slots):
            time.sleep(0.005)
        t2 = threading.Thread(target=lambda: _swallow(
            svc, "queued", 40))
        t2.start()
        deadline = time.monotonic() + 5.0
        while (time.monotonic() < deadline
               and len(svc.batcher.queue) < 1):
            time.sleep(0.005)
        # queue full: the next request must shed as HTTP 429
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": "shed me",
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 429
        assert int(e.value.headers["Retry-After"]) >= 1
        # the body repeats the back-off so relays (serve_router) can
        # rebuild the header they cannot see through http_json
        assert json.loads(e.value.read()).get("retry_after_s", 0) >= 1
        # healthz reports the shedding admission state in-band
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["reliability"]["admission"] == "shedding"
        assert health["reliability"]["queue_depth"] >= 1
        t1.join(timeout=30)
        t2.join(timeout=30)
    finally:
        httpd.shutdown()
        svc.shutdown()


def _swallow(svc, prompt, toks):
    try:
        svc.complete(prompt, toks, 0.0, timeout_s=30.0)
    except Exception:
        pass


# -------------------------------------------------------- slot leaks

def test_abandoned_stream_releases_slot_exactly_once():
    """The fixed bug: a stream abandoned between submit and first token
    frees its slot NOW (and a keep=True raced completion's session is
    released too) — no leak counter, slots all free."""
    svc, batcher = _service(slots=1, step_delay_s=0.01)
    before = _counter("serve_slot_leaks_total")
    try:
        uid, _, _chunks = svc.stream("abandon me", 500, 0.0,
                                     timeout_s=30.0, keep=True)
        svc.abandon_stream(uid)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            acct = batcher.slot_accounting()
            if (acct["active"] == 0 and acct["queued"] == 0
                    and acct["parked"] == 0):
                break
            time.sleep(0.01)
        assert acct == {"slots": 1, "active": 0, "parked": 0, "free": 1,
                        "queued": 0}
        assert _counter("serve_slot_leaks_total") == before
        # abandon after the request already finished: the parked session
        # in the dead chunk queue is released exactly once
        uid2, _, chunks2 = svc.stream("quick", 2, 0.0, timeout_s=30.0,
                                      keep=True)
        for _toks, c in chunks2:
            if c is not None:
                break  # finished; the tap queue was consumed though
        svc.abandon_stream(uid2)  # no-op: stream already closed
        assert batcher.slot_accounting()["parked"] == 1  # client owns it
        assert svc.batcher.release(c.session)
    finally:
        svc.shutdown()


def test_landed_keep_completion_abandon_releases_parked_session():
    """The landed-completion window: the scheduler delivered the final
    ("done", c) chunk (popping the stream registration) but the waiter
    died before consuming it. An abandon in that window must still find
    the parked session (landed registry) and release it; a waiter that
    never even reaches its abandon call is caught by the sweep's
    grace-window GC and counted as a leak."""
    svc, batcher = _service(slots=1, step_delay_s=0.01,
                            orphan_grace_s=1.5)
    before = _counter("serve_slot_leaks_total")
    try:
        # (1) orderly abandon after landing: released, NOT a leak
        uid, _, _chunks = svc.stream("landed", 2, 0.0, timeout_s=30.0,
                                     keep=True)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if batcher.slot_accounting()["parked"] == 1:
                break  # completion landed, session parked, never read
            time.sleep(0.01)
        assert batcher.slot_accounting()["parked"] == 1
        svc.abandon_stream(uid)  # chunks never consumed
        assert batcher.slot_accounting()["parked"] == 0
        assert _counter("serve_slot_leaks_total") == before
        # (2) waiter dies without abandoning: the sweep GC reclaims
        uid2, _, _chunks2 = svc.stream("landed2", 2, 0.0,
                                       timeout_s=30.0, keep=True)
        deadline = time.monotonic() + 8.0
        while time.monotonic() < deadline:
            if (_counter("serve_slot_leaks_total") > before
                    and batcher.slot_accounting()["parked"] == 0):
                break
            time.sleep(0.02)
        assert batcher.slot_accounting()["parked"] == 0
        assert _counter("serve_slot_leaks_total") == before + 1
    finally:
        svc.shutdown()


def test_slot_leak_injected_detected_and_reclaimed(tmp_path):
    """serve.slot_leak drill: abandon skips its release — the scheduler
    leak sweep must catch the orphaned slot, reclaim it, and count it."""
    events_lib.configure(str(tmp_path))
    fregistry.configure(specs=("serve.slot_leak@call=1",))
    svc, batcher = _service(slots=1, step_delay_s=0.01)
    before = _counter("serve_slot_leaks_total")
    try:
        uid, _, _chunks = svc.stream("leaky", 500, 0.0, timeout_s=30.0)
        svc.abandon_stream(uid)  # fault fires: walks away, no release
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if (_counter("serve_slot_leaks_total") > before
                    and batcher.slot_accounting()["active"] == 0):
                break
            time.sleep(0.01)
        assert _counter("serve_slot_leaks_total") == before + 1
        assert batcher.slot_accounting()["active"] == 0
        from pytorch_distributed_train_tpu.obs.events import load_events

        assert any(e["category"] == "serve" and e["name"] == "slot_leak"
                   for e in load_events(str(tmp_path)))
    finally:
        svc.shutdown()


def test_timeout_withdraws_nonstreamed_request():
    """The non-streamed flavor of the leak fix: a waiter that times out
    cancels its request instead of letting it decode on."""
    svc, batcher = _service(slots=1, step_delay_s=0.02)
    try:
        with pytest.raises(TimeoutError):
            svc.complete("slowpoke", 10_000, 0.0, timeout_s=0.2)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            acct = batcher.slot_accounting()
            if acct["active"] == 0 and acct["queued"] == 0:
                break
            time.sleep(0.01)
        assert acct["active"] == 0 and acct["queued"] == 0
        assert svc.plane.slo.snapshot()["outcomes"].get("timeout") == 1
    finally:
        svc.shutdown()


# ------------------------------------------------------ surfaces + soak

def test_healthz_reliability_section_over_http():
    svc, _ = _service(slots=2, step_delay_s=0.0)
    from http.server import ThreadingHTTPServer

    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                serve_http.make_handler(svc))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    try:
        svc.complete("warm", 4, 0.0, timeout_s=30.0)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            health = json.loads(r.read())
        rel = health["reliability"]
        assert rel["admission"] == "ok"
        assert rel["queue_depth"] == 0
        assert rel["slots"]["slots"] == 2 and rel["slots"]["free"] == 2
        assert rel["slo"]["ttft_s"]["n"] >= 1
        assert "goodput_s_decode" in rel["goodput"]
        assert health["stats"]["generated_tokens"] >= 4
        # metrics scrape carries the new series
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            body = r.read().decode()
        assert "serve_ttft_seconds_bucket" in body
        assert "serve_slots_free" in body
        assert 'serve_requests_total{outcome="ok"}' in body
    finally:
        httpd.shutdown()
        svc.shutdown()


def test_slot_accounting_on_real_batcher_classes():
    """The slot surface the plane relies on exists on every batcher
    (dense shown; paged/seq2seq inherit it)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu.config import (
        ModelConfig,
        PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.serving import ContinuousBatcher

    cfg = ModelConfig(name="llama", vocab_size=64, hidden_size=16,
                      num_layers=1, num_heads=2, num_kv_heads=2,
                      mlp_dim=32, max_seq_len=32)
    model = build_model(cfg, PrecisionConfig())
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 4), jnp.int32),
                        train=False)["params"]
    b = ContinuousBatcher(cfg, PrecisionConfig(), params, slots=2)
    assert b.slot_accounting() == {"slots": 2, "active": 0, "parked": 0,
                                   "free": 2, "queued": 0}
    uid = b.submit([1, 2, 3], 3)
    b.step()
    assert b.active_uids() == [uid]
    assert b.slot_accounting()["active"] == 1
    list(b.run())
    assert b.slot_accounting()["free"] == 2


def test_slo_soak_smoke():
    """Tier-1 smoke of tools/slo_soak.py: short seeded soak, all bounds
    hold (zero slot leaks, bounded shed, TTFT in budget)."""
    import slo_soak

    assert slo_soak.main(["--requests", "24", "--clients", "3",
                          "--step-delay", "0.001",
                          "--slow-decode",
                          "p=0.1:count=1000:delay=0.01"]) == 0


@pytest.mark.slow
def test_slo_soak_long():
    import slo_soak

    assert slo_soak.main(["--requests", "300", "--clients", "8",
                          "--seed", "7"]) == 0


def test_catalog_sync_serve_points_and_category():
    """docs ↔ registry ↔ emitters stay in sync with the serve additions
    (the satellites' three-way check)."""
    import check_events
    import check_fault_points

    assert {"serve.deadline", "serve.slot_leak",
            "serve.slow_decode"} <= set(fregistry.POINTS)
    assert "serve" in events_lib.CATEGORIES
    assert check_fault_points.main() == 0
    assert check_events.main() == 0
