"""Fleet health plane (obs/collector.py + obs/alerts.py +
tools/fleet_console.py): exposition parsing, windowed histogram
quantiles, store endpoint discovery, staleness (never vs stale), the
alert-rule lifecycle (fire → resolve, cooldown, sinks, overrides), the
sidecar port-collision fallback, memory telemetry, fleet_console
--snapshot/--offline smokes, and the ISSUE-13 acceptance drill
(2 subprocess fake-backend replicas + a tiny trainer, one launcher
store, zero static scrape config)."""

import json
import os
import queue as queue_mod
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import fleet_console  # noqa: E402
import timeline_report  # noqa: E402
from tiny import WORKER_HEAD  # noqa: E402

from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.alerts import (  # noqa: E402
    RULES,
    AlertEngine,
)
from pytorch_distributed_train_tpu.obs.collector import (  # noqa: E402
    FleetCollector,
    HistogramWindow,
    Target,
    family_by_label,
    family_value,
    parse_exposition,
)
from pytorch_distributed_train_tpu.obs.events import load_events  # noqa: E402
from pytorch_distributed_train_tpu.obs.registry import get_registry  # noqa: E402

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")


@pytest.fixture(autouse=True)
def _clean_events():
    yield
    events_lib._reset_for_tests()


class _StubCollector:
    """What AlertEngine actually reads: targets + stale_after_s."""

    def __init__(self, targets, stale_after_s=5.0):
        self.targets = list(targets)
        self.stale_after_s = stale_after_s


def _target(role="trainer", host="host0", addr="127.0.0.1:1", gen="0"):
    return Target({"role": role, "host": host, "addr": addr,
                   "gen": gen, "idx": 0})


class _TestClock:
    """Strictly increasing sample timestamps — real monotonic reads can
    collide with the engine's last-consumed watermark when pushes and
    evaluations interleave faster than the clock resolution."""

    t = time.monotonic()


def _push(t, series, *values):
    for v in values:
        _TestClock.t += 1e-3
        t.series[series].append((_TestClock.t, float(v)))


# ----------------------------------------------------------------- units

def test_parse_exposition_roundtrip():
    reg = get_registry()
    reg.counter("fx_requests_total", labels={"path": "a b"},
                help="x").inc(3)
    reg.gauge("fx_depth").set(2.5)
    reg.histogram("fx_lat_seconds").observe(0.003)
    fams = parse_exposition(reg.render())
    assert family_value(fams, "fx_requests_total",
                        {"path": "a b"}) == 3.0
    assert family_value(fams, "fx_depth") == 2.5
    assert family_value(fams, "fx_lat_seconds_count") == 1.0
    buckets = family_by_label(fams, "fx_lat_seconds_bucket", "le")
    assert buckets.get("+Inf") == 1.0
    # the 0.003 observation lands in the 0.004 cumulative bucket
    assert buckets.get("0.004") == 1.0


def test_histogram_window_quantile_fires_and_recovers():
    reg = get_registry()
    h = reg.histogram("fxw_ttft_seconds", help="x")
    win = HistogramWindow()
    for _ in range(20):
        h.observe(0.01)
    fams = parse_exposition(reg.render())
    assert win.observe(fams, "fxw_ttft_seconds") is None  # first = prime
    for _ in range(20):
        h.observe(0.01)
    fams = parse_exposition(reg.render())
    healthy = win.observe(fams, "fxw_ttft_seconds")
    assert healthy is not None and healthy <= 0.02
    for _ in range(20):
        h.observe(0.5)  # the storm
    fams = parse_exposition(reg.render())
    assert win.observe(fams, "fxw_ttft_seconds") >= 0.5
    for _ in range(20):
        h.observe(0.01)  # storm over: recovery is IMMEDIATE
    fams = parse_exposition(reg.render())
    assert win.observe(fams, "fxw_ttft_seconds") <= 0.02
    fams = parse_exposition(reg.render())
    assert win.observe(fams, "fxw_ttft_seconds") is None  # no new obs


def test_obs_endpoint_registry_roundtrip():
    from pytorch_distributed_train_tpu.elastic import (
        OBS_ENDPOINT_COUNT_KEY,
        discover_obs_endpoints,
        publish_obs_endpoint,
    )
    from pytorch_distributed_train_tpu.native.store import (
        StoreClient,
        StoreServer,
    )

    with StoreServer() as srv:
        c = StoreClient("127.0.0.1", srv.port)
        assert discover_obs_endpoints(c) == []
        assert publish_obs_endpoint(c, "trainer", "127.0.0.1:9100",
                                    host="host0", gen="0") == 0
        assert publish_obs_endpoint(c, "serving", "127.0.0.1:8000",
                                    host="host1", gen="1") == 1
        # a claimed-but-corrupt record is skipped, not fatal
        c.add(OBS_ENDPOINT_COUNT_KEY, 1)
        c.set("obs/endpoint/2", b"not json")
        eps = discover_obs_endpoints(c)
        assert [(e["role"], e["addr"], e["host"], e["gen"], e["idx"])
                for e in eps] == [
            ("trainer", "127.0.0.1:9100", "host0", "0", 0),
            ("serving", "127.0.0.1:8000", "host1", "1", 1)]
        # no host given, no PROCESS_ID env: the ADDR is the identity —
        # two ad-hoc replicas must not collapse into one "host0" target
        env_pid = os.environ.pop("PROCESS_ID", None)
        try:
            publish_obs_endpoint(c, "serving", "127.0.0.1:8001")
            assert discover_obs_endpoints(c)[-1]["host"] == "127.0.0.1:8001"
        finally:
            if env_pid is not None:
                os.environ["PROCESS_ID"] = env_pid
        c.close()
    assert discover_obs_endpoints(None) == []


def test_collector_scrapes_live_metrics_server():
    from pytorch_distributed_train_tpu.obs.exposition import MetricsServer

    reg = get_registry()
    srv = MetricsServer(0)  # port 0 = ephemeral now (satellite)
    try:
        reg.gauge("train_step").set(100)
        reg.gauge("train_loss").set(2.0)
        reg.gauge("train_goodput_pct").set(88.0)
        col = FleetCollector(
            store_factory=lambda: None,
            endpoints=[{"role": "trainer", "host": "host0",
                        "addr": f"127.0.0.1:{srv.port}", "gen": "0"}],
            poll_s=0.05, stale_after_s=5.0)
        col.poll()
        reg.gauge("train_step").set(110)
        time.sleep(0.05)
        col.poll()
        t = col.targets[0]
        assert t.state(time.monotonic(), 5.0) == "ok"
        assert t.latest("step") == 110.0
        assert t.latest("loss") == 2.0
        assert t.latest("steps_per_s") > 0
        # memory telemetry rides every scrape (obs/memory.py)
        assert "host_rss_bytes" in t.memory
        assert t.memory["host_rss_bytes"] > 0
        snap = col.snapshot()
        assert snap["targets"][0]["goodput_pct"] == 88.0
        assert snap["slowest_trainer"] == "host0"
    finally:
        srv.close()


def test_collector_staleness_never_vs_stale(tmp_path):
    events_lib.configure(str(tmp_path))
    body = b"train_step 1\n"
    alive = {"up": True}

    def fetch(url, timeout_s):
        if "9998" in url:  # the never-answering target
            raise OSError("connection refused")
        if not alive["up"]:
            raise OSError("connection refused")
        return 200, body if url.endswith("/metrics") else b"{}"

    col = FleetCollector(
        store_factory=lambda: None,
        endpoints=[
            {"role": "serving", "host": "hostA", "addr": "127.0.0.1:9999"},
            {"role": "serving", "host": "hostB", "addr": "127.0.0.1:9998"},
        ],
        # (1 s, not 0.2: the poll and the evaluation after it have to fall
        # inside it, on a box where six workers wait for the CPU)
        poll_s=0.05, stale_after_s=1.0, fetch=fetch)
    engine = AlertEngine()
    col.poll()
    engine.evaluate(col)
    by_host = {t.host: t for t in col.targets}
    now = time.monotonic()
    assert by_host["hostA"].state(now, 1.0) == "ok"
    assert by_host["hostB"].state(now, 1.0) == "never"
    alive["up"] = False
    time.sleep(1.2)
    col.poll()
    transitions = engine.evaluate(col)
    now = time.monotonic()
    assert by_host["hostA"].state(now, 1.0) == "stale"
    assert by_host["hostB"].state(now, 1.0) == "never"  # NOT stale
    fired = [(r["rule"], r["host"]) for r in transitions
             if r["event"] == "fired"]
    # the gone-stale host is blamed; the never-scraped one never is
    assert ("fleet_stale", "hostA") in fired
    assert not any(h == "hostB" for _r, h in fired)
    # recovery resolves it
    alive["up"] = True
    col.poll()
    transitions = engine.evaluate(col)
    assert any(r["event"] == "resolved" and r["rule"] == "fleet_stale"
               for r in transitions)


def test_anomaly_rule_lifecycle_and_cooldown(tmp_path):
    events_lib.configure(str(tmp_path))
    t = _target()
    col = _StubCollector([t])
    engine = AlertEngine(overrides={"loss_spike.min_samples": "4",
                                    "loss_spike.cooldown_s": "3600"})
    before = get_registry().get_value(
        "alerts_fired_total", {"rule": "loss_spike"}) or 0.0
    _push(t, "loss", 2.0, 2.1, 1.9, 2.0, 2.05)
    assert engine.evaluate(col) == []
    _push(t, "loss", 2e6)  # the spike
    trans = engine.evaluate(col)
    assert [r["event"] for r in trans] == ["fired"]
    assert trans[0]["rule"] == "loss_spike"
    assert get_registry().get_value(
        "alerts_firing", {"rule": "loss_spike"}) == 1.0
    assert get_registry().get_value(
        "alerts_fired_total", {"rule": "loss_spike"}) == before + 1
    assert engine.firing()[0]["host"] == "host0"
    # still spiking: no duplicate fire
    _push(t, "loss", 2e6, 3e6)
    assert engine.evaluate(col) == []
    # resolve_after consecutive healthy samples resolve it
    _push(t, "loss", 2.0, 2.0)
    trans = engine.evaluate(col)
    assert [r["event"] for r in trans] == ["resolved"]
    assert get_registry().get_value(
        "alerts_firing", {"rule": "loss_spike"}) == 0.0
    # a fresh spike inside the cooldown does NOT re-fire
    _push(t, "loss", 5e6)
    assert engine.evaluate(col) == []
    # journal carries the full lifecycle with host/gen tags
    names = [(e["name"], (e.get("detail") or {}).get("rule"),
              (e.get("detail") or {}).get("gen"))
             for e in load_events(str(tmp_path))
             if e["category"] == "alert"]
    assert ("fired", "loss_spike", "0") in names
    assert ("resolved", "loss_spike", "0") in names


def test_anomaly_quiet_series_resolves(tmp_path):
    """A firing anomaly rule over a SPARSE series (ttft_p95_s only
    samples while requests complete) must resolve once the series goes
    quiet for quiet_resolve_s — no traffic is not a regression, and a
    frozen-FIRING alert wedges every consumer that waits on resolution
    (the fleet controller's calm gate)."""
    events_lib.configure(str(tmp_path))
    t = _target(role="serving", host="hostQ")
    col = _StubCollector([t])
    engine = AlertEngine(overrides={
        "ttft_regression.min_samples": "4",
        "ttft_regression.quiet_resolve_s": "0.3"})

    def push(*vals):  # real-clock stamps: the quiet window is wall-time
        for v in vals:
            t.series["ttft_p95_s"].append((time.monotonic(), float(v)))
            time.sleep(0.002)

    push(0.05, 0.06, 0.05, 0.06, 0.05)
    assert engine.evaluate(col) == []
    push(0.9)
    trans = engine.evaluate(col)
    assert [r["event"] for r in trans] == ["fired"]
    fired_id = trans[0]["id"]
    # quiet window not yet elapsed: no evidence either way, no change
    assert engine.evaluate(col) == []
    assert engine.firing()[0]["rule"] == "ttft_regression"
    time.sleep(0.35)
    trans = engine.evaluate(col)
    assert [r["event"] for r in trans] == ["resolved"]
    assert trans[0]["id"] == fired_id  # the incident closes, same id
    assert engine.firing() == []
    alert_recs = [(e["name"], (e.get("detail") or {}).get("id"))
                  for e in load_events(str(tmp_path))
                  if e["category"] == "alert"]
    assert ("fired", fired_id) in alert_recs
    assert ("resolved", fired_id) in alert_recs


def test_threshold_and_rate_rules(tmp_path):
    events_lib.configure(str(tmp_path))
    t = _target(role="serving", host="hostS")
    t.last_ok_mono = time.monotonic()
    t.memory = {"host_available_bytes": 100 << 20,  # 100 MiB: risky
                "device_bytes_in_use": 95, "device_bytes_limit": 100}
    col = _StubCollector([t])
    engine = AlertEngine()
    trans = engine.evaluate(col)
    fired = {r["rule"] for r in trans if r["event"] == "fired"}
    assert "host_oom_risk" in fired
    assert "device_oom_risk" in fired  # 95% > 92%
    t.memory["host_available_bytes"] = 64 << 30
    t.memory["device_bytes_in_use"] = 10
    trans = engine.evaluate(col)
    assert {r["rule"] for r in trans
            if r["event"] == "resolved"} == {"host_oom_risk",
                                             "device_oom_risk"}
    # restart churn: only gens appearing AFTER the engine first saw
    # the target count — 3 new ones within the window fire
    t.gens.update({"1", "2"})
    trans = engine.evaluate(col)
    assert not any(r["rule"] == "restart_churn" for r in trans)  # 2 < 3
    t.gens.add("3")
    trans = engine.evaluate(col)
    assert any(r["rule"] == "restart_churn" and r["event"] == "fired"
               for r in trans)
    # a FRESH engine against a store that accumulated generations long
    # ago must not false-fire on history (console restart immunity)
    old = _target(role="serving", host="hostOld")
    old.last_ok_mono = time.monotonic()
    old.gens.update({"1", "2", "3", "4"})
    fresh = AlertEngine()
    trans = fresh.evaluate(_StubCollector([old]))
    assert not any(r["rule"] == "restart_churn" for r in trans)


def test_sinks_and_webhook(tmp_path):
    events_lib.configure(str(tmp_path / "ev"))
    posts = []

    class _Resp:
        status = 200

        def read(self):
            return b""

    def opener(req, timeout=None):
        posts.append((req.full_url, json.loads(req.data.decode())))
        return _Resp()

    sink = tmp_path / "alerts.jsonl"
    t = _target(host="hostX")
    t.last_ok_mono = time.monotonic()
    t.memory = {"host_available_bytes": 1}
    engine = AlertEngine(sink_path=str(sink),
                         webhook_url="http://hook.example/alert",
                         opener=opener)
    engine.evaluate(_StubCollector([t]))
    recs = [json.loads(line) for line in sink.read_text().splitlines()]
    assert recs and recs[0]["rule"] == "host_oom_risk"
    assert recs[0]["event"] == "fired" and "ts" in recs[0]
    assert posts and posts[0][0] == "http://hook.example/alert"
    assert posts[0][1]["host"] == "hostX"


def test_rule_override_validation():
    with pytest.raises(KeyError):
        AlertEngine(overrides={"no_such_rule.sigma": "1"})
    with pytest.raises(KeyError):
        AlertEngine(overrides={"loss_spike.not_a_field": "1"})
    e = AlertEngine(overrides={"loss_spike.sigma": "3.5",
                               "loss_spike.min_samples": "4",
                               "loss_spike.profile": "false"})
    r = e.rules["loss_spike"]
    assert r.sigma == 3.5 and r.min_samples == 4 and r.profile is False
    assert RULES["loss_spike"].sigma == 6.0  # catalog untouched


def test_metrics_server_port_collision_and_ephemeral():
    from pytorch_distributed_train_tpu.obs.exposition import MetricsServer

    a = MetricsServer(0)
    try:
        assert a.port > 0
        with pytest.raises(OSError):
            MetricsServer(a.port)  # hard bind still surfaces EADDRINUSE
        b = MetricsServer(0)  # ephemeral: any number of local workers
        try:
            assert b.port != a.port
        finally:
            b.close()
    finally:
        a.close()


def test_memory_gauges_in_exposition():
    from pytorch_distributed_train_tpu.obs.exposition import render_metrics

    fams = parse_exposition(render_metrics())
    assert (family_value(fams, "host_rss_bytes") or 0) > 0
    assert (family_value(fams, "host_available_bytes") or 0) > 0


# ------------------------------------------------------- console smokes

def test_fleet_console_snapshot_smoke(capsys):
    """The tier-1 CI smoke: --snapshot against one live static target
    renders the table, rollups and the alerts line, exit 0."""
    from pytorch_distributed_train_tpu.obs.exposition import MetricsServer

    get_registry().gauge("train_step").set(7)
    srv = MetricsServer(0)
    try:
        rc = fleet_console.main(
            ["--target", f"trainer=127.0.0.1:{srv.port}",
             "--snapshot", "--interval", "0.1"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fleet console: 1 target(s) (1 ok" in out
        assert "trainer" in out and "alerts:" in out
        rc = fleet_console.main(
            ["--target", f"trainer=127.0.0.1:{srv.port}",
             "--snapshot", "--interval", "0.1", "--format", "json"])
        snap = json.loads(capsys.readouterr().out)
        assert snap["targets"][0]["state"] == "ok"
        assert snap["alerts"] == []
    finally:
        srv.close()
    assert fleet_console.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out
    # no targets at all → usage error, not a hang
    os.environ.pop("TPUSTORE_ADDR", None)
    assert fleet_console.main(["--snapshot"]) == 2


def test_fleet_console_offline_report(tmp_path, capsys):
    events_lib.configure(str(tmp_path / "events"), who="fleet")
    events_lib.emit("alert", "fired", rule="ttft_regression",
                    host="host1", gen="0", value=0.4)
    events_lib.emit("alert", "resolved", rule="ttft_regression",
                    host="host1", gen="0")
    events_lib.emit("alert", "fired", rule="loss_spike",
                    host="host0", gen="0", value=9e9)
    events_lib._reset_for_tests()  # flush + close the journal
    rc = fleet_console.main(["--offline", "--run-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "2 fired over the journal; 1 still firing" in out
    assert "UNRESOLVED loss_spike on host0" in out
    assert "fleet_console: --offline needs" not in out
    assert fleet_console.main(["--offline"]) == 2


# ----------------------------------------------------- acceptance drill

TRAINER_WORKER = WORKER_HEAD + """
cfg.data.synthetic_size = 4096
cfg.data.batch_size = 8
cfg.total_steps = 100000
cfg.checkpoint.dir = {ckpt!r}
cfg.checkpoint.save_every_steps = 1000000
cfg.obs.metrics_port = -1
cfg.obs.profile_dir = {ckpt!r} + "/profiles"  # alert-triggered POST
# /profile captures must land in the drill tmp, not a cwd-relative dir
# step.straggle paces the loop (50 ms a step, whatever the box's load),
# so the loss storm's 300 steps last the 15 s and more in which the
# serving storm has to start: the two alerts overlap by construction
cfg.faults.inject = ("step.straggle@step=1:count=100000:delay=0.05",
                     "step.loss_spike@step=20:count=300")
t = Trainer(cfg)
try:
    t.fit()
finally:
    t.close()
time.sleep(600)
"""


def _spawn_replica(tmp_path, name, store_addr, proc_id, *, faults=""):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "TPUSTORE_ADDR": store_addr,
           "PROCESS_ID": str(proc_id),
           "NUM_PROCESSES": "4",
           "PDTT_EVENTS_DIR": str(tmp_path / "events"),
           "PDTT_PROFILE_BACKEND": "fake",
           "PDTT_PROFILE_DIR": str(tmp_path / f"prof_{name}")}
    if faults:
        env["PDTT_FAULTS"] = faults
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve_http.py"),
         "--fake-backend", "--fake-step-delay", "0.01", "--port", "0",
         "--slots", "4", "--advertise", "--drain-grace", "5"],
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


def test_e2e_drill_fleet_alerts(tmp_path):
    """THE ISSUE-13 acceptance drill: 2 subprocess fake-backend serving
    replicas + a tiny trainer, all self-registered in one launcher
    store; the collector discovers all three with zero static config;
    serve.slow_decode storms replica A and step.loss_spike storms the
    trainer → ttft_regression and loss_spike FIRE (journaled with gen
    tags, gauges 1), the console snapshot names replica A slowest and
    lists both; the storms exhaust → both RESOLVE (gauges 0, resolved
    journaled) and timeline_report renders the alert→capture→resolve
    chain; SIGKILL replica A → fleet_stale fires and the console marks
    it STALE — while a registered-but-never-up endpoint stays 'never'
    and is never blamed."""
    from pytorch_distributed_train_tpu.elastic import (
        publish_obs_endpoint,
    )
    from pytorch_distributed_train_tpu.native.store import (
        StoreClient,
        StoreServer,
    )

    events_dir = tmp_path / "events"
    reg = get_registry()

    def gauge(rule):
        return reg.get_value("alerts_firing", {"rule": rule})

    with StoreServer() as srv:
        store_addr = f"127.0.0.1:{srv.port}"
        # a claimed endpoint that never comes up: the never-scraped case
        c = StoreClient("127.0.0.1", srv.port)
        publish_obs_endpoint(c, "serving", "127.0.0.1:1",
                             host="ghost", gen="0")
        c.close()
        proc_a, addr_a = _spawn_replica(
            tmp_path, "a", store_addr, 1,
            faults="serve.slow_decode@call=150:count=40:delay=0.3")
        proc_b, addr_b = _spawn_replica(tmp_path, "b", store_addr, 2)
        trainer_script = tmp_path / "trainer_worker.py"
        trainer_script.write_text(TRAINER_WORKER.format(
            ckpt=str(tmp_path / "ckpt")))
        tenv = {**os.environ, "JAX_PLATFORMS": "cpu",
                "TPUSTORE_ADDR": store_addr,
                "PDTT_EVENTS_DIR": str(events_dir)}
        for k in ("PROCESS_ID", "NUM_PROCESSES"):
            tenv.pop(k, None)
        trainer_log = open(tmp_path / "trainer.log", "w")
        proc_t = subprocess.Popen(
            [sys.executable, str(trainer_script)], env=tenv, cwd=REPO,
            stdout=trainer_log, stderr=subprocess.STDOUT)

        events_lib.configure(str(events_dir), who="fleet")
        # stale_after sized for a 2-core box where the trainer, two
        # replicas, traffic and the collector all contend; min_rel=10
        # on loss_spike makes early-training organic loss movement
        # unfirable while the 1e6x storm still trivially fires
        col = FleetCollector(
            store_factory=fleet_console._store_factory(store_addr),
            poll_s=0.15, stale_after_s=8.0)
        engine = AlertEngine(
            profile_on_alert=True, profile_cooldown_s=1.0,
            overrides={"loss_spike.min_samples": "4",
                       "loss_spike.min_rel": "10",
                       "loss_spike.cooldown_s": "5",
                       "ttft_regression.min_samples": "4",
                       "ttft_regression.min_rel": "0.5",
                       "ttft_regression.cooldown_s": "5",
                       "trainer_step_stalled.for_s": "3600"})
        stop = threading.Event()

        def loop():
            while not stop.is_set():
                try:
                    col.poll()
                    engine.evaluate(col)
                except Exception:
                    pass
                time.sleep(0.15)

        collector_thread = threading.Thread(target=loop, daemon=True)
        collector_thread.start()

        traffic_stop = threading.Event()

        def traffic(addr, ci):
            i = 0
            while not traffic_stop.is_set():
                body = json.dumps({"prompt": f"drill {ci}-{i}",
                                   "max_tokens": 6}).encode()
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        f"http://{addr}/v1/completions", data=body,
                        headers={"Content-Type": "application/json"}),
                        timeout=30).read()
                except Exception:
                    pass
                i += 1
                time.sleep(0.02)

        tthreads = []
        try:
            # -- discovery: all four records, zero static config. The
            # trainer runs OUTSIDE the launcher env contract here (no
            # PROCESS_ID), so its identity is its advertised addr —
            # the collapse-proof default the endpoint registry uses
            # for ad-hoc processes.
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                roles = sorted((t.role, t.host) for t in col.targets)
                if len(roles) >= 4 and any(r == "trainer"
                                           for r, _h in roles):
                    break
                time.sleep(0.2)
            roles = sorted((t.role, t.host) for t in col.targets)
            trainer_host = next((h for r, h in roles if r == "trainer"),
                                None)
            assert trainer_host is not None, roles
            assert ":" in trainer_host, trainer_host  # addr identity
            assert ("serving", "host1") in roles, roles
            assert ("serving", "host2") in roles, roles
            assert ("serving", "ghost") in roles, roles

            # traffic starts only once the trainer's loss storm is
            # FIRING (min_rel=10 means a fire IS the storm, never
            # organic early-training movement), so the serve storm —
            # which begins a few hundred decode quanta into the
            # traffic — lands inside the loss storm and the two alerts
            # overlap deterministically
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                if any(a["rule"] == "loss_spike"
                       for a in engine.firing()):
                    break
                time.sleep(0.05)
            assert any(a["rule"] == "loss_spike"
                       for a in engine.firing()), \
                "trainer loss storm never fired the fleet rule"
            tthreads = [
                threading.Thread(target=traffic, args=(a, i), daemon=True)
                for i, a in ((0, addr_a), (1, addr_a), (2, addr_b))]
            for t in tthreads:
                t.start()

            # -- both alert rules FIRE, simultaneously
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                firing = {(a["rule"], a["host"])
                          for a in engine.firing()}
                # (the engine sets a gauge after it lists the alert)
                if (("loss_spike", trainer_host) in firing
                        and ("ttft_regression", "host1") in firing
                        and gauge("loss_spike")
                        and gauge("ttft_regression")):
                    break
                time.sleep(0.05)
            firing = {(a["rule"], a["host"]) for a in engine.firing()}
            assert ("loss_spike", trainer_host) in firing, firing
            assert ("ttft_regression", "host1") in firing, firing
            assert reg.get_value("alerts_firing",
                                 {"rule": "loss_spike"}) == 1.0
            assert reg.get_value("alerts_firing",
                                 {"rule": "ttft_regression"}) == 1.0

            # -- console snapshot: replica A slowest, both alerts listed
            snap = col.snapshot()
            text = fleet_console.render_snapshot(snap, engine.firing())
            assert "slowest serving replica: host1" in text, text
            assert "FIRING loss_spike" in text
            assert "FIRING ttft_regression" in text

            # -- storms exhaust → both RESOLVE
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                firing = {(a["rule"], a["host"])
                          for a in engine.firing()}
                if (("loss_spike", trainer_host) not in firing
                        and ("ttft_regression", "host1") not in firing
                        and not gauge("loss_spike")
                        and not gauge("ttft_regression")):
                    break
                time.sleep(0.1)
            firing = {(a["rule"], a["host"]) for a in engine.firing()}
            assert ("loss_spike", trainer_host) not in firing, firing
            assert ("ttft_regression", "host1") not in firing, firing
            assert reg.get_value("alerts_firing",
                                 {"rule": "loss_spike"}) == 0.0
            assert reg.get_value("alerts_firing",
                                 {"rule": "ttft_regression"}) == 0.0

            # -- journal: fired + resolved with gen tags; the chain
            events = load_events(str(events_dir))
            alert_recs = [(e["name"], (e.get("detail") or {}).get("rule"))
                          for e in events if e["category"] == "alert"]
            assert ("fired", "loss_spike") in alert_recs
            assert ("fired", "ttft_regression") in alert_recs
            assert ("resolved", "loss_spike") in alert_recs
            assert ("resolved", "ttft_regression") in alert_recs
            assert any(e["category"] == "alert"
                       and (e.get("detail") or {}).get("gen") is not None
                       for e in events)
            assert ("profile_requested" in
                    {n for n, _ in alert_recs}), alert_recs
            chains = "\n".join(timeline_report.alert_chains(events))
            assert "FIRED" in chains
            assert "-> capture requested" in chains, chains
            assert "-> resolved after" in chains, chains
            # alert transitions are timeline landmarks
            lines = "\n".join(timeline_report.timeline_lines(
                events, width=20))
            assert "ALERT" in lines

            # -- SIGKILL replica A: staleness fires, console marks it;
            #    the ghost endpoint stays 'never' and is never blamed
            proc_a.kill()
            proc_a.wait(timeout=30)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                firing = {(a["rule"], a["host"])
                          for a in engine.firing()}
                if ("fleet_stale", "host1") in firing:
                    break
                time.sleep(0.25)
            firing = {(a["rule"], a["host"]) for a in engine.firing()}
            assert ("fleet_stale", "host1") in firing, firing
            assert ("fleet_stale", "ghost") not in firing
            text = fleet_console.render_snapshot(col.snapshot(),
                                                 engine.firing())
            assert re.search(r"host1\s+serving\s+\S+\s+STALE", text), text
            assert re.search(r"ghost\s+serving\s+\S+\s+NEVER", text), text
        finally:
            stop.set()
            traffic_stop.set()
            collector_thread.join(timeout=10)
            for t in tthreads:
                t.join(timeout=30)
            for p in (proc_a, proc_b, proc_t):
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
            trainer_log.close()
