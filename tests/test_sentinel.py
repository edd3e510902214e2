"""Training health sentinel (sentinel/ — ISSUE 3): in-graph numeric
guard (step.nan skips exactly one update), loss-spike auto-rewind to the
last verified checkpoint with LR cooldown, cross-host hang diagnosis
(blamed host + cluster flight-recorder dump + distinct rc + gang
restart), plus the satellites: mid-epoch exact resume for both loaders,
the elastic windowed restart budget + backoff, serve_http graceful
drain, and the docs<->registry fault-point cross-check."""

import dataclasses
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from tiny import WORKER_HEAD, tiny_cfg

from pytorch_distributed_train_tpu.config import DataConfig, TrainConfig
from pytorch_distributed_train_tpu.faults import registry as fregistry
from pytorch_distributed_train_tpu.obs.registry import get_registry
from pytorch_distributed_train_tpu.sentinel.numeric import (
    SpikeDetector,
    cooldown_scale,
    cooldown_transform,
    scale_cooldown,
)

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")

CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}


@pytest.fixture(autouse=True)
def _clean_schedule(monkeypatch):
    monkeypatch.delenv("RESTART_GENERATION", raising=False)
    monkeypatch.delenv(fregistry.ENV_VAR, raising=False)
    fregistry._reset_for_tests()
    yield
    fregistry._reset_for_tests()


# ------------------------------------------------------------ spike detector
def test_spike_detector_inactive_until_min_samples():
    d = SpikeDetector(window=8, sigma=4.0, min_samples=4)
    for v in (1.0, 1.1, 1.0):
        assert not d.is_spike(1e9)  # window too small to judge anything
        d.add(v)
    d.add(1.05)
    assert d.is_spike(1e9)


def test_spike_detector_flags_outlier_not_jitter():
    d = SpikeDetector(window=16, sigma=6.0, min_samples=4, min_rel=0.1)
    for v in (2.0, 2.1, 1.9, 2.05, 2.0, 1.95):
        d.add(v)
    assert not d.is_spike(2.15)   # ordinary jitter
    assert d.is_spike(20.0)       # 10x divergence
    assert d.is_spike(0.01)       # collapse is as suspicious as explosion


def test_spike_detector_healthy_only_window_and_reset():
    d = SpikeDetector(window=8, sigma=6.0, min_samples=3, min_rel=0.1)
    for v in (1.0, 1.0, 1.0):
        d.add(v)
    # spikes are NOT added — the baseline must not drift up with the
    # divergence it is supposed to catch
    for _ in range(5):
        assert d.is_spike(50.0)
    assert len(d.window) == 3
    d.reset()
    assert not d.is_spike(50.0)  # fresh window: inactive again


# ------------------------------------------------------------- lr cooldown
def test_cooldown_transform_scales_updates():
    import jax.numpy as jnp
    import optax

    tx = optax.chain(optax.sgd(1.0), cooldown_transform())
    params = {"w": jnp.ones((4,))}
    state = tx.init(params)
    grads = {"w": jnp.full((4,), 2.0)}
    upd, state = tx.update(grads, state, params)
    np.testing.assert_allclose(np.asarray(upd["w"]), -2.0)
    assert cooldown_scale(state) == 1.0
    state = scale_cooldown(state, 0.5)
    upd, state = tx.update(grads, state, params)
    np.testing.assert_allclose(np.asarray(upd["w"]), -1.0)
    state = scale_cooldown(state, 0.5)  # compounds across rewinds
    assert cooldown_scale(state) == pytest.approx(0.25)


def test_cooldown_absent_is_none_and_passthrough():
    import jax.numpy as jnp
    import optax

    tx = optax.sgd(1.0)
    state = tx.init({"w": jnp.ones(2)})
    assert cooldown_scale(state) is None
    assert scale_cooldown(state, 0.5) is state or True  # structure unchanged


# --------------------------------------------------------------- e2e helpers
def _tiny_cfg(tmp_path, tag: str) -> TrainConfig:
    return tiny_cfg(
        f"checkpoint.dir={tmp_path}/ckpt-{tag}", "checkpoint.max_to_keep=20",
        f"obs.jsonl_path={tmp_path}/metrics-{tag}.jsonl",
        "sentinel.enabled=true")


def _params_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(jax.device_get(a)),
                               jax.tree.leaves(jax.device_get(b))))


def _summary_rows(path):
    rows = []
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r.get("tag") == "summary":
                rows.append(r)
    return rows


# ---------------------------------------------------- e2e: nan skip (gate)
def test_step_nan_skips_exactly_one_update(tmp_path):
    """Acceptance path 1: ``step.nan@step=N`` poisons one batch; the
    in-graph guard skips that update only — params at N+1 equal params
    at N, every other consecutive pair differs — and the skip is
    counted under reason=nonfinite."""
    from pytorch_distributed_train_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = _tiny_cfg(tmp_path, "nan")
    cfg.total_steps = 6
    cfg.checkpoint.save_every_steps = 1
    cfg.faults.inject = ("step.nan@step=3",)
    before = get_registry().get_value(
        "sentinel_skipped_steps_total", {"reason": "nonfinite"}) or 0.0
    t = Trainer(cfg)
    params = {}  # post-step param snapshots, keyed by completed step
    orig_step = t.train_step

    def capture(state, batch, rng):
        new_state, m = orig_step(state, batch, rng)
        params[len(params) + 1] = jax.device_get(new_state.params)
        return new_state, m

    t.train_step = capture
    t.fit()
    t.close()
    assert get_registry().get_value(
        "sentinel_skipped_steps_total", {"reason": "nonfinite"}) == before + 1

    # exactly the poisoned step's update is a no-op
    assert _params_equal(params[3], params[4])
    for a, b in ((1, 2), (2, 3), (4, 5), (5, 6)):
        assert not _params_equal(params[a], params[b]), (a, b)
    # the nonfinite step put the state under suspicion: its cadence save
    # (step 4) is withheld, every healthy step's save lands
    mgr = CheckpointManager(dataclasses.replace(cfg.checkpoint))
    assert sorted(mgr.mgr.all_steps()) == [1, 2, 3, 5, 6]
    mgr.close()
    # no rewind was needed for a single absorbed NaN
    assert _summary_rows(cfg.obs.jsonl_path)[-1]["rewinds"] == 0


# ------------------------------------------- e2e: spike -> rewind + cooldown
def test_loss_spike_streak_rewinds_with_cooldown(tmp_path, capfd):
    """Acceptance path 2: ``sentinel.max_consecutive_bad`` observed
    spikes trigger an auto-rewind to the newest VERIFIED checkpoint,
    the LR cooldown factor lands in the optimizer state (and the train
    log), and the run still completes its horizon."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = _tiny_cfg(tmp_path, "spike")
    cfg.total_steps = 8
    cfg.checkpoint.save_every_steps = 2
    cfg.sentinel.spike_min_samples = 3
    cfg.sentinel.max_consecutive_bad = 2
    # organic step-to-step jitter can't reach 50% of median; the drill's
    # 1e6 inflation can — the rewind fires on injected spikes only
    cfg.sentinel.spike_min_rel = 0.5
    cfg.faults.inject = ("step.loss_spike@step=4:count=2",)
    before = get_registry().family_total("sentinel_rewinds_total")
    t = Trainer(cfg)
    t.fit()
    t.close()
    out = capfd.readouterr().out

    assert get_registry().family_total(
        "sentinel_rewinds_total") == before + 1
    assert t._rewinds == 1
    # spikes observed at steps 5 and 6 -> rewind lands on the step-4 save
    assert "[sentinel] rewinding from step 6 to verified step 4" in out
    # cooldown applied once and persisted in the live opt state
    assert cooldown_scale(t.state.opt_state) == pytest.approx(
        cfg.sentinel.lr_cooldown_factor)
    summary = _summary_rows(cfg.obs.jsonl_path)[-1]
    assert summary["rewinds"] == 1
    # the horizon was still reached after the rewind replay
    last_train = [json.loads(line)
                  for line in open(cfg.obs.jsonl_path)
                  if json.loads(line).get("tag") == "train"][-1]
    assert last_train["step"] == 8
    assert last_train["lr_cooldown_scale"] == pytest.approx(0.5)
    assert last_train["lr"] == pytest.approx(0.05 * 0.5)
    # the flight recorder kept the diagnosis
    kinds = [e[1] for e in t.recorder.events()]
    assert "sentinel_rewind" in kinds and "sentinel_bad_step" in kinds


# ------------------------------------------------- liveness plane (units)
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


def test_hang_monitor_blames_stalest_host_and_orders_dump():
    from pytorch_distributed_train_tpu.sentinel.liveness import LivenessPlane

    data: dict = {}
    exits: list[int] = []
    dumps: list[str] = []

    class _Rec:
        def dump(self, reason="", suffix=""):
            dumps.append(reason)

        def record(self, *a, **k):
            pass

    plane = LivenessPlane(
        hang_timeout_s=0.4, poll_s=0.1, exit_code=43,
        recorder=_Rec(), spans=None,
        store_factory=lambda: _FakeStore(data),
        rank=0, world=2, gen="0", exit_fn=exits.append)
    assert plane.start()
    try:
        deadline = time.time() + 10.0
        while time.time() < deadline and not exits:
            # rank 0 keeps beating; rank 1 heartbeat once, then silence
            plane.beat(int(time.time() * 10) % 1000)
            data.setdefault(
                "sentinel/0/hb/1",
                json.dumps({"step": 2, "ts": 0.0}).encode())
            time.sleep(0.05)
        assert exits == [43]
        assert plane.blamed and plane.blamed["rank"] == 1
        assert "sentinel/0/dump" in data  # cluster-wide dump ordered
        assert dumps and "host 1" in dumps[0]
    finally:
        plane.stop()


def test_watcher_obeys_dump_order_while_main_thread_wedged():
    """The dump path that matters: the WATCHER thread dumps the local
    flight recorder on the store order, independent of the (possibly
    wedged) main thread, and stamps the blame in the reason header."""
    from pytorch_distributed_train_tpu.sentinel.liveness import LivenessPlane

    data = {
        "sentinel/0/dump":
            json.dumps({"rank": 1, "detail": "no heartbeat"}).encode(),
    }
    dumps: list[str] = []

    class _Rec:
        def dump(self, reason="", suffix=""):
            dumps.append(reason)

    plane = LivenessPlane(
        hang_timeout_s=5.0, poll_s=0.05, exit_code=43, recorder=_Rec(),
        store_factory=lambda: _FakeStore(data), rank=1, world=2, gen="0")
    assert plane.start()
    try:
        deadline = time.time() + 5.0
        while time.time() < deadline and not dumps:
            time.sleep(0.02)
        assert dumps and "host 1" in dumps[0]
        assert json.loads(
            data["sentinel/0/phase/1"].decode())["spans"] is not None
    finally:
        plane.stop()


def test_liveness_pulse_beats_outside_step_cadence():
    """pulse() keeps a host alive through long NON-step phases (eval,
    final save): it publishes regardless of the heartbeat_every_steps
    cadence, carrying the last known step."""
    from pytorch_distributed_train_tpu.sentinel.liveness import LivenessPlane

    data: dict = {}
    plane = LivenessPlane(
        hang_timeout_s=5.0, every_steps=4,
        store_factory=lambda: _FakeStore(data), rank=0, world=1, gen="0")
    plane._beat_store = _FakeStore(data)
    plane.active = True
    plane.beat(3)  # off-cadence: records the step but publishes nothing
    assert "sentinel/0/hb/0" not in data
    plane.pulse()  # eval/save progress: publishes despite the cadence
    assert json.loads(data["sentinel/0/hb/0"].decode())["step"] == 3
    plane.beat(4)  # on-cadence step beat
    assert json.loads(data["sentinel/0/hb/0"].decode())["step"] == 4


# --------------------------------------------- e2e: host hang (gang-level)
HANG_WORKER = WORKER_HEAD + """
from pytorch_distributed_train_tpu.elastic import worker_store

cfg.total_steps = 6
cfg.checkpoint.dir = os.path.join({out!r}, f"ckpt-{{rank}}")
cfg.checkpoint.save_every_steps = 2
cfg.obs.jsonl_path = os.path.join({out!r}, f"metrics-{{rank}}.jsonl")
# (The suite runs with the compile cache off, and this drill needs it
# off: the diagnosis ends rank 0 with os._exit, and an exit that lands
# in a cache write poisons every later generation.)
# The timeout has to outlast every phase of a HEALTHY host that does not
# beat: the first compile after the restore's pulse and a synchronous
# save, each a second or two alone and several under six xdist workers.
# At 4 s the monitor blamed whichever host was saving or compiling, the
# gang restarted into a generation without the fault, and the drill
# never saw its wedge. Liveness semantics are unchanged: only the
# drill's patience grows.
cfg.sentinel.hang_timeout_s = 12.0
cfg.sentinel.hang_poll_s = 0.25
# generation 0 only. Host 0 must still be beating when host 1 wedges: a
# host that has finished waits in the barrier below without a beat, and
# the monitor blames whichever host goes stale first. The tiny trainer's
# six steps take a fraction of a second, so the hosts enter fit together
# and host 0 spends 4.5 s (in sleeps far under the timeout) on its steps
# 4 to 6, which covers the skew of two compiles on a loaded box.
cfg.faults.inject = (("host.hang@step=3",) if rank == 1 else
                     ("step.straggle@step=4:count=3:delay=1.5",))
t = Trainer(cfg)
worker_store().barrier(f"built/{{gen}}", world, rank, timeout_ms=120000)
t.fit()
# SPMD stand-in: finished hosts block on their peers the way a real
# collective would — rank 0 sits here while rank 1 is wedged, and only
# the hang monitor (still running; it outlives fit) can end the wait.
worker_store().barrier(f"fitdone/{{gen}}", world, rank, timeout_ms=120000)
t.close()
"""


def test_host_hang_diagnosed_dumped_and_gang_restarted(tmp_path, capfd):
    """Acceptance path 3: an injected ``host.hang`` on rank 1 produces a
    blamed-host diagnosis (id + the open ``fault.host_hang`` span), a
    CLUSTER-wide flight-recorder dump (the wedged host's own watcher
    thread writes one too), a distinct rc the elastic agent restarts
    on, and a generation-1 resume that completes the run."""
    from pytorch_distributed_train_tpu.elastic import ElasticAgent, LaunchConfig

    script = tmp_path / "worker.py"
    script.write_text(HANG_WORKER.format(out=str(tmp_path)))
    cfg = LaunchConfig(nprocs=2, max_restarts=2, monitor_interval_s=0.2,
                       shutdown_grace_s=2.0, backoff_base_s=0.05,
                       backoff_max_s=0.1, env=CPU_ENV)
    rc = ElasticAgent(cfg, [sys.executable, str(script)]).run()
    out, err = capfd.readouterr()
    assert rc == 0, (rc, out[-1000:], err[-1000:])

    # 1. blamed-host diagnosis, naming the wedged host AND its open span
    assert "[sentinel] host 1 appears HUNG" in out, out[-2000:]
    assert "fault.host_hang" in out
    # 2. the distinct rc drove the gang restart
    assert "worker failed (rc=43)" in out
    assert "gen 1" in out
    # 3. cluster-wide dump: BOTH hosts wrote flight files, each carrying
    #    the blame header — including the wedged host, whose main thread
    #    could not have written anything
    for rank in (0, 1):
        ckpt = tmp_path / f"ckpt-{rank}"
        dump_files = [f for f in os.listdir(ckpt)
                      if f.startswith("flight_")]
        assert dump_files, (rank, os.listdir(ckpt))
        text = "\n".join((ckpt / f).read_text() for f in dump_files)
        assert "cluster hang dump: host 1" in text, (rank, text[:500])
    # 4. generation 1 completed the horizon on both ranks
    for rank in (0, 1):
        steps = [json.loads(line)["step"]
                 for line in open(tmp_path / f"metrics-{rank}.jsonl")
                 if json.loads(line).get("tag") == "train"]
        assert max(steps) == 6, (rank, sorted(set(steps)))


# ------------------------------------- satellite: mid-epoch exact resume
def _loader_cfg(**kw) -> DataConfig:
    return DataConfig(dataset="synthetic_images", batch_size=16,
                      num_workers=0, seed=7, synthetic_size=128, **kw)


def _assert_byte_identical_resume(loader, start_batch=3):
    full = list(loader.epoch(0))
    resumed = list(loader.epoch(0, start_batch=start_batch))
    assert len(resumed) == len(full) - start_batch
    for i, (a, b) in enumerate(zip(full[start_batch:], resumed)):
        assert set(a) == set(b)
        for k in a:
            assert a[k].dtype == b[k].dtype, (i, k)
            assert a[k].tobytes() == b[k].tobytes(), (
                f"batch {start_batch + i} field {k!r} diverged on resume")


def test_threads_loader_mid_epoch_resume_byte_identical():
    from pytorch_distributed_train_tpu.config import ModelConfig
    from pytorch_distributed_train_tpu.data.datasets import build_dataset
    from pytorch_distributed_train_tpu.data.pipeline import HostDataLoader

    cfg = _loader_cfg()
    ds = build_dataset(cfg, ModelConfig(image_size=8, num_classes=10),
                       train=True)
    loader = HostDataLoader(ds, cfg, train=True, num_hosts=1, host_id=0)
    _assert_byte_identical_resume(loader)


def test_grain_loader_mid_epoch_resume_byte_identical():
    from pytorch_distributed_train_tpu.config import ModelConfig
    from pytorch_distributed_train_tpu.data.datasets import build_dataset
    from pytorch_distributed_train_tpu.data.grain_pipeline import (
        GrainHostDataLoader,
    )

    cfg = _loader_cfg(loader="grain")
    ds = build_dataset(cfg, ModelConfig(image_size=8, num_classes=10),
                       train=True)
    loader = GrainHostDataLoader(ds, cfg, train=True, num_hosts=1, host_id=0)
    _assert_byte_identical_resume(loader)


# ------------------------- satellite: elastic windowed budget + backoff
def test_backoff_delay_grows_caps_and_jitters():
    from pytorch_distributed_train_tpu.elastic import _backoff_delay

    flat = lambda: 0.0  # noqa: E731
    assert _backoff_delay(1, 1.0, 30.0, 0.25, rand=flat) == 1.0
    assert _backoff_delay(3, 1.0, 30.0, 0.25, rand=flat) == 4.0
    assert _backoff_delay(10, 1.0, 30.0, 0.25, rand=flat) == 30.0  # capped
    assert _backoff_delay(1, 1.0, 30.0, 0.5, rand=lambda: 1.0) == 1.5


WINDOWED_WORKER = """
import os, sys, time
gen = int(os.environ["RESTART_GENERATION"])
out = {out!r}
if gen in (0, 1):
    sys.exit(9)          # crash loop: two fast failures burn budget
if gen == 2:
    time.sleep(0.6)      # healthy past the stable window...
    sys.exit(9)          # ...then an unrelated failure
open(os.path.join(out, f"gen{{gen}}-ok"), "w").write("done")
"""


def test_windowed_restart_budget_resets_after_stable_run(tmp_path, capfd):
    """max_restarts=2 with an absolute counter dies at generation 2's
    failure; the WINDOWED budget forgives it because that generation ran
    past stable_window_s, so generation 3 spawns and succeeds."""
    from pytorch_distributed_train_tpu.elastic import ElasticAgent, LaunchConfig

    script = tmp_path / "worker.py"
    script.write_text(WINDOWED_WORKER.format(out=str(tmp_path)))
    cfg = LaunchConfig(nprocs=1, max_restarts=2, monitor_interval_s=0.05,
                       stable_window_s=0.4, backoff_base_s=0.01,
                       backoff_max_s=0.02)
    rc = ElasticAgent(cfg, [sys.executable, str(script)]).run()
    out, _ = capfd.readouterr()
    assert rc == 0, out[-800:]
    assert (tmp_path / "gen3-ok").exists()
    assert "resetting restart budget" in out


# ---------------------------------------- satellite: serve_http drain
class _FakeDrainService:
    """Minimal BatcherService stand-in: one blockable completion."""

    def __init__(self):
        self.release = threading.Event()
        self.error = None
        self.max_new_default = 8
        self.tok = None

    def healthy(self):
        return True

    def stats(self):
        return {"fake": 1}

    def complete(self, prompt, max_tokens, temperature, **kw):
        assert self.release.wait(30.0)
        return {"text": "done", "finish_reason": "length", "session": None,
                "usage": {"prompt_tokens": 1, "completion_tokens": 1}}

    def shutdown(self):
        pass


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def _post(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_serve_http_graceful_drain(tmp_path):
    """SIGTERM-drain contract: in-flight requests finish with 200, new
    requests get a retryable 503, /healthz flips to ``draining``, and
    the server exits cleanly once drained."""
    from http.server import ThreadingHTTPServer

    import serve_http

    service = _FakeDrainService()
    server = ThreadingHTTPServer(("127.0.0.1", 0), None)
    drain = serve_http.GracefulDrain(server, service, grace_s=20.0)
    server.RequestHandlerClass = serve_http.make_handler(service, drain)
    port = server.server_address[1]
    serve_thread = threading.Thread(target=server.serve_forever, daemon=True)
    serve_thread.start()

    assert _get(port, "/healthz") == (200, {"status": "ok",
                                            "stats": {"fake": 1}})
    inflight: dict = {}

    def _inflight_post():
        inflight["result"] = _post(port, {"prompt": "hi", "max_tokens": 4})

    t = threading.Thread(target=_inflight_post, daemon=True)
    t.start()
    # wait for the request to be admitted (inflight counter visible)
    deadline = time.time() + 10.0
    while time.time() < deadline and drain._inflight == 0:
        time.sleep(0.02)
    assert drain._inflight == 1

    drain.request_drain()
    code, body = _get(port, "/healthz")
    assert (code, body["status"]) == (503, "draining")
    code, body = _post(port, {"prompt": "rejected"})
    assert code == 503 and "draining" in body["error"]

    service.release.set()  # let the in-flight request finish
    t.join(timeout=20)
    assert inflight["result"][0] == 200
    assert inflight["result"][1]["text"] == "done"
    serve_thread.join(timeout=20)  # drain thread shut the server down
    assert not serve_thread.is_alive()


# ------------------------- satellite: docs <-> registry fault-point sync
def test_fault_point_catalog_in_sync_with_registry():
    import check_fault_points

    assert check_fault_points.documented_points() == set(fregistry.POINTS)
    assert check_fault_points.main() == 0
