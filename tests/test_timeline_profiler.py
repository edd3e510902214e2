"""Event journal + managed profiler plane + timeline report (ISSUE 5):
every trigger path (cadence, trigger file, store-coordinated cross-host,
loss-spike / straggler / regression auto-capture, ring retention)
driven deterministically against a FAKE profiler backend, the
docs<->emitters category cross-check, and the acceptance e2e: a seeded
``step.loss_spike`` drill producing a journaled anomaly, an automatic
capture with an xplane top-ops summary, and a timeline_report showing
the anomaly->capture->recovery causal chain."""

import json
import os
import sys
import time

import pytest
from tiny import tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from pytorch_distributed_train_tpu.config import ObsConfig
from pytorch_distributed_train_tpu.faults import registry as fregistry
from pytorch_distributed_train_tpu.obs import events as events_lib
from pytorch_distributed_train_tpu.obs import profiler as profiler_lib
from pytorch_distributed_train_tpu.obs.registry import get_registry


@pytest.fixture(autouse=True)
def _clean_planes(monkeypatch):
    monkeypatch.delenv("RESTART_GENERATION", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    monkeypatch.delenv(events_lib.ENV_VAR, raising=False)
    monkeypatch.delenv(fregistry.ENV_VAR, raising=False)
    events_lib._reset_for_tests()
    fregistry._reset_for_tests()
    yield
    events_lib._reset_for_tests()
    fregistry._reset_for_tests()


# ---------------------------------------------------------------- fakes
class FakeProfilerBackend:
    """Injectable capture object: records start/stop, optionally drops
    a synthetic xplane dump so the top-ops summary path runs for real."""

    def __init__(self, write_xplane: bool = True):
        self.calls: list[tuple[str, str]] = []
        self.write_xplane = write_xplane
        self._logdir = None

    def start(self, logdir: str) -> None:
        os.makedirs(logdir, exist_ok=True)
        self._logdir = logdir
        self.calls.append(("start", logdir))

    def stop(self) -> None:
        self.calls.append(("stop", self._logdir))
        if not (self.write_xplane and self._logdir):
            return
        try:
            from tensorflow.tsl.profiler.protobuf import xplane_pb2
        except ImportError:  # summary degrades, capture still lands
            return
        xs = xplane_pb2.XSpace()
        plane = xs.planes.add(name="/device:TPU:0")
        for i, name in enumerate(["%fusion.1", "%dot.2"], start=1):
            m = plane.event_metadata[i]
            m.id, m.name = i, name
        line = plane.lines.add(name="XLA Ops")
        for md, dur_ms in ((1, 3.0), (2, 7.0)):
            ev = line.events.add()
            ev.metadata_id = md
            ev.duration_ps = int(dur_ms * 1e9)
        d = os.path.join(self._logdir, "plugins", "profile", "fake")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
            f.write(xs.SerializeToString())


class _FakeStore:
    """Dict-backed stand-in for native/store.py StoreClient."""

    def __init__(self, data):
        self.data = data

    def set(self, key, value):
        self.data[key] = value

    def get(self, key, timeout_ms=0):
        if key not in self.data:
            raise TimeoutError(key)
        return self.data[key]

    def close(self):
        pass


def _obs(tmp_path, **kw) -> ObsConfig:
    cfg = ObsConfig(profile_dir=str(tmp_path / "profiles"),
                    events_dir=str(tmp_path / "events"))
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def _profiler(tmp_path, **kw) -> profiler_lib.ManagedProfiler:
    cfg = _obs(tmp_path, **kw)
    events_lib.configure(cfg.events_dir)
    p = profiler_lib.ManagedProfiler(
        cfg, run_dir=str(tmp_path), backend=FakeProfilerBackend())
    p.start()
    return p


def _events(tmp_path):
    return events_lib.load_events(str(tmp_path / "events"))


# -------------------------------------------------------------- journal
def test_event_journal_schema_counter_and_catalog(tmp_path):
    before = get_registry().get_value(
        "obs_events_total", {"category": "sentinel"}) or 0.0
    j = events_lib.configure(str(tmp_path / "ev"), who="host3", gen="2")
    j.emit("sentinel", "rewind", step=6, to=4, lr_scale=0.5)
    j.emit("lifecycle", "fit_start")  # step-less record
    with pytest.raises(KeyError):
        j.emit("typo_category", "x")
    recs = events_lib.load_events(str(tmp_path / "ev"))
    assert [r["name"] for r in recs] == ["rewind", "fit_start"]
    r = recs[0]
    assert r["host"] == "host3" and r["gen"] == "2" and r["step"] == 6
    assert r["category"] == "sentinel"
    assert r["detail"] == {"to": 4, "lr_scale": 0.5}
    assert isinstance(r["ts"], float)
    assert recs[1]["step"] is None
    assert get_registry().get_value(
        "obs_events_total", {"category": "sentinel"}) == before + 1
    # append-only across "generations": a second configure appends
    j2 = events_lib.configure(str(tmp_path / "ev"), who="host3", gen="3")
    j2.emit("sentinel", "rewind", step=9)
    recs = events_lib.load_events(str(tmp_path / "ev"))
    assert len(recs) == 3 and recs[-1]["gen"] == "3"


def test_event_journal_without_sink_counts_only(tmp_path):
    before = get_registry().family_total("obs_events_total")
    j = events_lib.configure(None)
    j.emit("fault", "step.crash", step=1)  # must not raise, no file
    assert get_registry().family_total("obs_events_total") == before + 1
    assert j.path is None


# ----------------------------------------------------- trigger: cadence
def test_cadence_trigger_bounded_windows_and_summary(tmp_path):
    p = _profiler(tmp_path, profile_every_steps=4, profile_window_steps=2)
    for step in range(1, 12):
        p.on_step(step)
    p.finish()
    starts = [c for c in p.backend.calls if c[0] == "start"]
    stops = [c for c in p.backend.calls if c[0] == "stop"]
    assert len(starts) == 2 and len(stops) == 2  # steps 4-6 and 8-10
    assert "capture_step00000004_cadence" in starts[0][1]
    assert "capture_step00000008_cadence" in starts[1][1]
    # each completed capture was summarized through the xplane reader
    for _, d in starts:
        text = open(os.path.join(d, "top_ops.txt")).read()
        assert "/device:TPU:0" in text and "matmul" in text
    names = [(e["category"], e["name"]) for e in _events(tmp_path)]
    assert names.count(("profile", "capture_start")) == 2
    assert names.count(("profile", "capture_end")) == 2
    end = [e for e in _events(tmp_path)
           if e["name"] == "capture_end"][0]
    assert any("/device:TPU:0" in line
               for line in end["detail"]["summary"])


# ------------------------------------------------- trigger: local file
def test_trigger_file_opens_window_and_is_consumed(tmp_path):
    p = _profiler(tmp_path, profile_window_steps=3)
    trig = p.trigger_file
    p.on_step(1)
    assert not p.backend.calls  # dormant without a trigger
    open(trig, "w").close()
    p.on_step(2)
    assert not os.path.exists(trig)  # consumed
    # the request keeps the default few-step lead (so store-coordinated
    # peers can adopt before the window opens): capture at step 4
    assert not p.backend.calls
    p.on_step(3)
    assert not p.backend.calls
    p.on_step(4)
    assert p.backend.calls[0][0] == "start"
    assert "capture_step00000004_trigger_file" in p.backend.calls[0][1]
    p.on_step(5)
    p.on_step(6)
    assert [c[0] for c in p.backend.calls] == ["start"]
    p.on_step(7)  # window (3 steps) closes
    assert [c[0] for c in p.backend.calls] == ["start", "stop"]
    p.finish()


# ---------------------------------------- trigger: store-coordinated
def test_store_request_adopted_by_all_hosts_same_window(tmp_path):
    shared: dict = {}
    profs = []
    for rank in range(2):
        cfg = _obs(tmp_path, profile_window_steps=2)
        p = profiler_lib.ManagedProfiler(
            cfg, run_dir=str(tmp_path), backend=FakeProfilerBackend(),
            store_factory=lambda: _FakeStore(shared), rank=rank, world=2)
        p.start()
        profs.append(p)
    events_lib.configure(str(tmp_path / "events"))
    req = profs[0].request_capture("ondemand", start_step=5)
    assert profiler_lib.REQUEST_KEY in shared
    deadline = time.time() + 5.0
    while time.time() < deadline and not all(
            p._pending is not None for p in profs):
        time.sleep(0.02)
    assert all(p._pending is not None and p._pending.id == req.id
               for p in profs), "watchers did not adopt the request"
    for p in profs:
        p.on_step(4)
        assert not p.backend.calls  # before the coordinated start step
        p.on_step(5)
        p.on_step(7)
    dirs = {p.backend.calls[0][1] for p in profs}
    assert len(dirs) == 1, "hosts captured different windows"
    assert "capture_step00000005_ondemand" in dirs.pop()
    for p in profs:
        assert [c[0] for c in p.backend.calls] == ["start", "stop"]
        p.finish()
    # a stale request must not re-fire on a fresh profiler (restart)
    cfg = _obs(tmp_path)
    p3 = profiler_lib.ManagedProfiler(
        cfg, run_dir=str(tmp_path), backend=FakeProfilerBackend(),
        store_factory=lambda: _FakeStore(shared), rank=0, world=2)
    p3.start()
    time.sleep(0.5)
    assert p3._pending is None
    p3.finish()


# ------------------------------------------------ trigger: regressions
def test_step_time_regression_autocapture_and_cooldown(tmp_path):
    p = _profiler(tmp_path, profile_on_anomaly=True,
                  profile_window_steps=1, profile_cooldown_steps=50,
                  profile_regress_min_samples=4)
    before = get_registry().get_value(
        "profiler_anomalies_total", {"kind": "step_time_regression"}) or 0.0
    for step in range(1, 9):
        p.on_step(step)
        p.observe_step_time(0.01 + 0.0001 * step, step)
    p.observe_step_time(0.5, 9)  # 50x the baseline: a straggling step
    assert get_registry().get_value(
        "profiler_anomalies_total",
        {"kind": "step_time_regression"}) == before + 1
    p.on_step(10)  # adopts the auto request (start_step = 9+1)
    assert p.backend.calls and p.backend.calls[0][0] == "start"
    assert "step_time_regression" in p.backend.calls[0][1]
    p.on_step(11)  # window closes
    # firing RESET the detector (re-baseline: a persistent shift must
    # not journal one anomaly per step forever) — refill the window,
    # then a second spike journals but the cooldown withholds a capture
    for step in range(12, 17):
        p.observe_step_time(0.01, step)
    p.observe_step_time(0.5, 17)
    p.on_step(18)
    p.on_step(19)
    assert [c[0] for c in p.backend.calls] == ["start", "stop"]
    kinds = [e["name"] for e in _events(tmp_path)
             if e["category"] == "anomaly"]
    assert kinds == ["step_time_regression", "step_time_regression"]
    p.finish()


def test_stall_regression_respects_absolute_floor(tmp_path):
    p = _profiler(tmp_path, profile_on_anomaly=True,
                  profile_stall_min_pct=5.0,
                  profile_regress_min_samples=16)
    # noisy near-zero baseline: relative spikes below the floor never fire
    for step, pct in enumerate((0.0, 0.01, 0.0, 0.02, 0.01, 4.0), 1):
        p.observe_stall_pct(pct, step)
    assert not [e for e in _events(tmp_path)
                if e["category"] == "anomaly"]
    p.observe_stall_pct(60.0, 7)  # over the floor AND a spike
    assert [e["name"] for e in _events(tmp_path)
            if e["category"] == "anomaly"] == ["input_stall_regression"]
    p.finish()


def test_straggler_blame_predicate():
    agg = {"step_time_p50_med": 100.0, "step_time_p50_max": 250.0,
           "step_time_p50_max_host": 3}
    assert profiler_lib.straggler_blame(agg, 2.0) == 3
    assert profiler_lib.straggler_blame(agg, 3.0) is None  # under ratio
    assert profiler_lib.straggler_blame(agg, 0.0) is None  # disabled
    assert profiler_lib.straggler_blame({}, 2.0) is None   # single host


def test_straggler_anomaly_opens_capture(tmp_path):
    """The trainer's straggler hook funnels into anomaly('straggler'):
    journaled, counted, and (with profile_on_anomaly) a window opens."""
    p = _profiler(tmp_path, profile_on_anomaly=True,
                  profile_window_steps=1)
    p.anomaly("straggler", 50, host=3, p50_max=250.0, p50_med=100.0)
    p.on_step(51)
    p.on_step(52)
    assert [c[0] for c in p.backend.calls] == ["start", "stop"]
    assert "capture_step00000051_straggler" in p.backend.calls[0][1]
    ev = [e for e in _events(tmp_path) if e["category"] == "anomaly"][0]
    assert ev["name"] == "straggler" and ev["detail"]["host"] == 3
    p.finish()


# -------------------------------------------------------- ring + legacy
def test_ring_retention_keeps_newest_captures(tmp_path):
    p = _profiler(tmp_path, profile_every_steps=2,
                  profile_window_steps=1, profile_ring=2)
    for step in range(1, 13):
        p.on_step(step)
        time.sleep(0.01)  # distinct mtimes for the recency sort
    p.finish()  # closes the step-12 capture, then GCs
    dirs = sorted(d for d in os.listdir(p.profile_dir)
                  if d.startswith("capture_"))
    assert dirs == ["capture_step00000010_cadence",
                    "capture_step00000012_cadence"]
    assert get_registry().family_total("profiler_ring_evicted_total") > 0
    assert any(e["name"] == "ring_evict" for e in _events(tmp_path))


def test_legacy_window_shim_writes_profile_dir_root(tmp_path):
    p = _profiler(tmp_path, profile_start_step=3, profile_num_steps=2,
                  profile_ring=1)
    for step in range(1, 7):
        p.on_step(step)
    p.finish()
    assert p.backend.calls[0] == ("start", str(tmp_path / "profiles"))
    assert [c[0] for c in p.backend.calls] == ["start", "stop"]
    # the legacy dir is exempt from the ring: nothing evicted it
    assert os.path.isdir(str(tmp_path / "profiles"))
    starts = [e for e in _events(tmp_path) if e["name"] == "capture_start"]
    assert starts[0]["detail"]["reason"] == "legacy"
    assert starts[0]["step"] == 3


def test_adhoc_time_bounded_capture(tmp_path):
    p = _profiler(tmp_path)
    logdir = p.capture_for_seconds(0.1, reason="http")
    assert logdir and "capture_adhoc_http" in logdir
    assert p.capture_for_seconds(0.1) is None  # one window at a time
    deadline = time.time() + 5.0
    while time.time() < deadline and len(p.backend.calls) < 2:
        time.sleep(0.02)
    assert [c[0] for c in p.backend.calls] == ["start", "stop"]
    p.finish()


def test_adhoc_window_owned_by_timer_not_step_loop(tmp_path):
    """The sidecar's time-bounded capture (window=0, start_step=-1)
    must survive step boundaries — only its timer (or finish) ends it."""
    p = _profiler(tmp_path)
    assert p.capture_for_seconds(30.0, reason="http")
    p.on_step(100)
    p.on_step(101)
    assert [c[0] for c in p.backend.calls] == ["start"]
    p.finish()  # cancels the timer, closes the window
    assert [c[0] for c in p.backend.calls] == ["start", "stop"]


# ------------------------------------------------------ tools + harness
def test_event_catalog_in_sync_with_docs_and_emitters():
    import check_events

    assert check_events.main() == 0


def test_conftest_faulthandler_armed():
    import faulthandler

    assert faulthandler.is_enabled()


def test_obs_report_events_section(tmp_path, capsys):
    import obs_report

    j = events_lib.configure(str(tmp_path / "events"), who="host0")
    j.emit("sentinel", "rewind", step=6, to=4)
    j.emit("profile", "capture_end", step=8, reason="loss_spike",
           dir="x/capture_step00000006_loss_spike")
    (tmp_path / "metrics.jsonl").write_text(json.dumps(
        {"tag": "train", "step": 8, "goodput_pct": 50.0,
         "step_time_ms_p50": 10.0}) + "\n")
    assert obs_report.main(["--run-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "sentinel=1" in out and "profile=1" in out
    assert "last rewind" in out and "rewind@step 6" in out
    assert "last capture" in out and "capture_end@step 8" in out
    assert "last restart" in out  # present, with a '-' placeholder


def test_timeline_report_merges_hosts_and_builds_chains(tmp_path, capsys):
    import timeline_report

    evdir = tmp_path / "events"
    j0 = events_lib.configure(str(evdir), who="host0", gen="0")
    j0.emit("anomaly", "loss_spike", step=5, loss=9.9)
    j0.emit("profile", "capture_end", step=7, reason="loss_spike",
            dir="p/capture_step00000006_loss_spike")
    j0.emit("sentinel", "rewind", step=7, to=4)
    j1 = events_lib.configure(str(evdir), who="agent0", gen="0")
    j1.emit("elastic", "spawn", gen=0, world=2)
    (tmp_path / "trace.json").write_text(json.dumps({"traceEvents": [
        {"name": "train.step", "ph": "X", "ts": 1.0, "dur": 5.0,
         "pid": 9, "tid": "MainThread"}]}))
    out_path = tmp_path / "merged.json"
    rc = timeline_report.main(["--run-dir", str(tmp_path),
                               "--out", str(out_path)])
    assert rc == 0
    out = capsys.readouterr().out
    # both writers merged, chronological, chain assembled
    assert "2 writers" in out
    assert "anomaly chains (1):" in out
    chain = [line for line in out.splitlines()
             if "loss_spike@step 5" in line][0]
    assert "capture_step00000006_loss_spike" in chain
    assert "sentinel.rewind@step 7" in chain
    merged = json.loads(out_path.read_text())
    evs = merged["traceEvents"]
    assert any(e.get("ph") == "X" for e in evs)  # spans passed through
    instants = [e for e in evs if e.get("ph") == "i"]
    assert any(e["name"] == "anomaly.loss_spike" for e in instants)
    pids = {e.get("pid") for e in instants}
    assert len(pids) == 2  # one process row per journal writer
    assert any(e.get("ph") == "M" and e["args"]["name"] == "host0"
               for e in evs)


def test_timeline_report_missing_events_dir(tmp_path, capsys):
    import timeline_report

    assert timeline_report.main(["--run-dir", str(tmp_path)]) == 2


# ------------------------------------------------- acceptance e2e drill
def test_e2e_spike_drill_journals_captures_and_reports(tmp_path, capfd):
    """ISSUE-5 acceptance: a seeded ``step.loss_spike@step=4`` drill
    produces (1) a journaled anomaly event, (2) an AUTOMATIC profiler
    capture whose journaled summary carries the xplane top-ops report,
    and (3) a timeline_report output showing the
    anomaly->capture->recovery causal chain — all on the CPU mesh with
    the fake backend."""
    import timeline_report

    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = tiny_cfg(
        "total_steps=8", f"checkpoint.dir={tmp_path}/ckpt",
        "checkpoint.save_every_steps=2",
        f"obs.jsonl_path={tmp_path}/ckpt/metrics.jsonl",
        f"obs.profile_dir={tmp_path}/ckpt/profiles",
        "obs.profile_on_anomaly=true", "obs.profile_window_steps=2",
        "sentinel.enabled=true")
    cfg.sentinel.spike_min_samples = 3
    cfg.sentinel.spike_min_rel = 0.5
    cfg.sentinel.max_consecutive_bad = 2
    cfg.faults.inject = ("step.loss_spike@step=4:count=2",)
    t = Trainer(cfg)
    t.profiler.backend = FakeProfilerBackend()
    t.fit()
    t.close()

    evs = events_lib.load_events(str(tmp_path / "ckpt" / "events"))
    names = [(e["category"], e["name"]) for e in evs]
    # (1) the drill fired and the anomaly was journaled
    assert ("fault", "step.loss_spike") in names
    # both observed spikes journal an anomaly; the cooldown means only
    # the FIRST opens a capture
    # (a loaded worker may journal an ``input_stall_regression`` of its
    # own between them: the drill's contract is its two spikes, in order)
    anomalies = [e for e in evs if e["category"] == "anomaly"
                 and e["name"] == "loss_spike"]
    assert len(anomalies) == 2
    assert anomalies[0]["step"] < anomalies[1]["step"]
    assert anomalies[0]["ts"] <= anomalies[1]["ts"]
    # (2) an automatic capture opened and its journaled summary carries
    # the xplane top-ops report of the fake dump
    assert [c[0] for c in t.profiler.backend.calls] == ["start", "stop"]
    assert "loss_spike" in t.profiler.backend.calls[0][1]
    end = [e for e in evs if e["name"] == "capture_end"]
    assert len(end) == 1 and end[0]["detail"]["reason"] == "loss_spike"
    assert any("/device:TPU:0" in line
               for line in end[0]["detail"]["summary"])
    assert os.path.exists(os.path.join(
        t.profiler.backend.calls[0][1], "top_ops.txt"))
    # the recovery (sentinel rewind) is journaled after the anomaly
    rewinds = [e for e in evs if (e["category"], e["name"])
               == ("sentinel", "rewind")]
    assert len(rewinds) == 1 and rewinds[0]["detail"]["to"] == 4
    assert rewinds[0]["ts"] >= anomalies[0]["ts"]
    # (3) timeline_report assembles the causal chain on one screen
    capfd.readouterr()
    assert timeline_report.main(["--run-dir", cfg.checkpoint.dir]) == 0
    out = capfd.readouterr().out
    chain = [line for line in out.splitlines()
             if "loss_spike@step" in line and "->" in line][0]
    assert "capture_step" in chain          # anomaly -> capture ...
    assert "sentinel.rewind@step" in chain  # ... -> recovery
    # the one-screen timeline marks the fault, the capture and the rewind
    for needle in ("FAULT", "ANOMALY", "PROFILE", "SENTINEL"):
        assert needle in out
