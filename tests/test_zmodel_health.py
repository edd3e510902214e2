"""Model-health plane acceptance drills (ISSUE 20): the in-graph stats
under the overlap shard_map path, and THE fleet drill — a seeded
``step.grad_spike`` storm on a subprocess trainer fires the
``grad_norm_spike`` early-warning alert (journaled with a minted id,
gauge 1, profile capture requested) while the loss-based sentinel never
records a bad step, the model-health monitor arms the rewind on the
warning streak, the alert resolves once the storm exhausts, and
``tools/postmortem.py --alert <id>`` renders the grad-norm/update-ratio
series around the incident from the collector's TSDB write-through.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import fleet_console  # noqa: E402
from tiny import WORKER_HEAD  # noqa: E402

from pytorch_distributed_train_tpu import steps as steps_lib  # noqa: E402
from pytorch_distributed_train_tpu.config import (  # noqa: E402
    MeshConfig,
    ModelConfig,
    OptimConfig,
    PrecisionConfig,
)
from pytorch_distributed_train_tpu.losses import get_loss_fn  # noqa: E402
from pytorch_distributed_train_tpu.models.registry import (  # noqa: E402
    build_model,
)
from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.alerts import AlertEngine  # noqa: E402
from pytorch_distributed_train_tpu.obs.collector import (  # noqa: E402
    FleetCollector,
)
from pytorch_distributed_train_tpu.obs.events import load_events  # noqa: E402
from pytorch_distributed_train_tpu.obs.registry import (  # noqa: E402
    get_registry,
)
from pytorch_distributed_train_tpu.obs.tsdb import (  # noqa: E402
    TimeSeriesStore,
)
from pytorch_distributed_train_tpu.optim import make_optimizer  # noqa: E402
from pytorch_distributed_train_tpu.parallel.mesh import build_mesh  # noqa: E402
from pytorch_distributed_train_tpu.parallel.partition import (  # noqa: E402
    rules_for_model,
)
from pytorch_distributed_train_tpu.train_state import TrainState  # noqa: E402

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")


@pytest.fixture(autouse=True)
def _clean_events():
    yield
    events_lib._reset_for_tests()


# ---------------------------------------- overlap shard_map stat parity

MODEL_CFG = ModelConfig(name="vit_b16", num_classes=10, image_size=8,
                        patch_size=4, hidden_size=32, num_layers=2,
                        num_heads=4, mlp_dim=64, dropout_rate=0.0)
OPT_CFG = OptimConfig(name="adamw", learning_rate=1e-3,
                      schedule="constant", warmup_steps=0,
                      weight_decay=0.01, grad_clip_norm=1.0)


def _batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "image": jnp.asarray(rng.standard_normal((n, 8, 8, 3)),
                             jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, n), jnp.int32),
    }


def test_overlap_health_stats_match_gspmd(devices8):
    """model_health under the shard_map overlap path: params are
    replicated and the bucketed reducer lands the reduced grads before
    the stats pass, so every health scalar must match the GSPMD step's
    (and the actual-update oracle) — sharding is layout, not math."""
    mesh = build_mesh(MeshConfig(data=8), devices8)
    model = build_model(MODEL_CFG, PrecisionConfig())
    loss_fn = get_loss_fn("softmax_xent")
    tx, _ = make_optimizer(OPT_CFG, total_steps=100)
    rules = rules_for_model("vit_b16")

    def init_state(rng):
        variables = model.init({"params": rng}, jnp.zeros((2, 8, 8, 3)),
                               train=False)
        return TrainState.create(params=variables["params"], tx=tx)

    shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(mesh, rules, shape)

    def fresh():
        return jax.jit(init_state, out_shardings=sharding)(
            jax.random.PRNGKey(0))

    axes = ("data", "fsdp")
    reduce_grads, _buckets = steps_lib.overlap_grad_reducer(
        shape.params, 1, axes)
    ostep = steps_lib.jit_overlap_train_step(
        steps_lib.make_train_step(
            model, loss_fn, tx, grad_accum_steps=2, model_health=True,
            reduce_grads=reduce_grads,
            reduce_metrics=steps_lib.metrics_reducer(axes)),
        mesh, sharding)
    gstep = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, loss_fn, tx,
                                  grad_accum_steps=2, model_health=True),
        mesh, sharding)

    o_state, g_state = fresh(), fresh()
    o_old = jax.device_get(o_state.params)
    for i in range(2):
        o_old = jax.device_get(o_state.params)
        o_state, o_m = ostep(o_state, _batch(seed=i),
                             jax.random.PRNGKey(42))
        g_state, g_m = gstep(g_state, _batch(seed=i),
                             jax.random.PRNGKey(42))
    o_m = {k: float(v) for k, v in jax.device_get(o_m).items()}
    g_m = {k: float(v) for k, v in jax.device_get(g_m).items()}
    health = [k for k in g_m if k.startswith(
        ("grad_norm", "param_norm", "update_norm", "update_ratio"))]
    assert "update_ratio_max" in health and any("/" in k for k in health)
    for k in health:
        assert o_m[k] == pytest.approx(g_m[k], rel=1e-3, abs=1e-6), k
    # the overlap step's update_norm is the actual applied update
    o_new = jax.device_get(o_state.params)
    diff = np.sqrt(sum(
        float(np.sum(np.square(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))
        for a, b in zip(jax.tree.leaves(o_new), jax.tree.leaves(o_old))))
    assert o_m["update_norm"] == pytest.approx(diff, rel=1e-3)
    # and the training itself still matches the GSPMD step
    for a, b in zip(jax.tree.leaves(o_new),
                    jax.tree.leaves(jax.device_get(g_state.params))):
        np.testing.assert_allclose(a, b, atol=1e-5)


# ------------------------------------------------ THE acceptance drill

TRAINER_WORKER = WORKER_HEAD + """
cfg.data.synthetic_size = 4096
cfg.data.batch_size = 8
cfg.total_steps = 100000
cfg.checkpoint.dir = {ckpt!r}
cfg.checkpoint.save_every_steps = 10
cfg.obs.metrics_port = -1
cfg.obs.profile_dir = {ckpt!r} + "/profiles"
cfg.obs.model_health = True
cfg.sentinel.enabled = True
cfg.sentinel.spike_min_rel = 0.5
cfg.faults.inject = ("step.grad_spike@step=40:count=40",)
t = Trainer(cfg)
try:
    t.fit()
finally:
    t.close()
time.sleep(600)
"""


def _alert_events(events_dir, name, rule):
    return [e for e in load_events(str(events_dir))
            if e.get("category") == "alert" and e.get("name") == name
            and (e.get("detail") or {}).get("rule") == rule]


def test_e2e_drill_grad_spike_early_warning(tmp_path):
    """THE ISSUE-20 acceptance drill: a seeded ``step.grad_spike``
    storm on a subprocess trainer (loss UNTOUCHED) fires the
    ``grad_norm_spike`` fleet rule — journaled with a minted id, gauge
    1, profile capture requested — while the sentinel journals no
    loss-based bad step; the trainer's own monitor arms the rewind on
    the warning streak; the alert resolves after the storm; and the
    postmortem CLI renders the grad-norm/update-ratio series around
    the incident from the TSDB write-through."""
    from pytorch_distributed_train_tpu.native.store import StoreServer

    events_dir = tmp_path / "events"
    events_dir.mkdir()
    reg = get_registry()
    aid = None
    with StoreServer() as srv:
        store_addr = f"127.0.0.1:{srv.port}"
        trainer_script = tmp_path / "trainer_worker.py"
        trainer_script.write_text(TRAINER_WORKER.format(
            ckpt=str(tmp_path / "ckpt")))
        tenv = {**os.environ, "JAX_PLATFORMS": "cpu",
                "TPUSTORE_ADDR": store_addr,
                "PDTT_EVENTS_DIR": str(events_dir),
                "PDTT_PROFILE_BACKEND": "fake"}
        for k in ("PROCESS_ID", "NUM_PROCESSES", "PDTT_FAULTS"):
            tenv.pop(k, None)
        trainer_log = open(tmp_path / "trainer.log", "w")
        proc_t = subprocess.Popen(
            [sys.executable, str(trainer_script)], env=tenv, cwd=REPO,
            stdout=trainer_log, stderr=subprocess.STDOUT)

        events_lib.configure(str(events_dir), who="fleet")
        hist = TimeSeriesStore(str(tmp_path / "tsdb"))
        col = FleetCollector(
            store_factory=fleet_console._store_factory(store_addr),
            poll_s=0.15, stale_after_s=8.0, history=hist)
        # min_rel=10: organic early-training movement (grad norms AND
        # the loss) is unfirable, the 1e3x storm trivially fires — the
        # drill's whole point is that ONLY the grad rule sees it
        engine = AlertEngine(
            profile_on_alert=True, profile_cooldown_s=1.0,
            overrides={"grad_norm_spike.min_samples": "4",
                       "grad_norm_spike.min_rel": "10",
                       "grad_norm_spike.cooldown_s": "5",
                       "loss_spike.min_samples": "4",
                       "loss_spike.min_rel": "10",
                       # (a loaded box's slow step fired this rule a second
                       # before the storm, and its profile request took the
                       # grad rule's inside the engine's cooldown)
                       "step_time_regression.min_rel": "10",
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

        threading.Thread(target=loop, daemon=True).start()
        try:
            # -- the storm fires the early-warning rule
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                # (the engine sets the gauge after it lists the alert)
                if any(a["rule"] == "grad_norm_spike"
                       for a in engine.firing()) and reg.get_value(
                        "alerts_firing", {"rule": "grad_norm_spike"}):
                    break
                time.sleep(0.05)
            assert any(a["rule"] == "grad_norm_spike"
                       for a in engine.firing()), \
                "grad storm never fired the fleet rule"
            assert reg.get_value("alerts_firing",
                                 {"rule": "grad_norm_spike"}) == 1.0
            fired = _alert_events(events_dir, "fired", "grad_norm_spike")
            assert fired, "fired never journaled"
            aid = (fired[0].get("detail") or {}).get("id")
            assert aid and aid.startswith("grad_norm_spike@"), aid

            # -- BEFORE any loss-based verdict: the loss was never
            # touched, so at fire time (and for the whole drill) the
            # sentinel has recorded no bad step and the loss rule is
            # quiet — the precursor beat the lagging indicator
            evs = load_events(str(events_dir))
            assert not [e for e in evs
                        if e.get("category") == "sentinel"
                        and e.get("name") == "bad_step"]
            assert not _alert_events(events_dir, "fired", "loss_spike")

            # -- profile capture requested against the trainer
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if _alert_events(events_dir, "profile_requested",
                                 "grad_norm_spike"):
                    break
                time.sleep(0.05)
            assert _alert_events(events_dir, "profile_requested",
                                 "grad_norm_spike")

            # -- the trainer's own monitor warned and ARMED the rewind
            # on the streak (journaled under the model category with
            # optimizer context)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                evs = load_events(str(events_dir))
                if any(e.get("category") == "model"
                       and e.get("name") == "rewind_armed"
                       for e in evs):
                    break
                time.sleep(0.1)
            model_evs = [e for e in load_events(str(events_dir))
                         if e.get("category") == "model"]
            warnings = [e for e in model_evs
                        if e["name"] == "early_warning"]
            assert warnings
            assert any("lr" in (e.get("detail") or {}) for e in warnings)
            assert any(e["name"] == "rewind_armed" for e in model_evs)

            # -- the storm exhausts: the alert RESOLVES
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                if not any(a["rule"] == "grad_norm_spike"
                           for a in engine.firing()) and not reg.get_value(
                        "alerts_firing", {"rule": "grad_norm_spike"}):
                    break
                time.sleep(0.1)
            assert not any(a["rule"] == "grad_norm_spike"
                           for a in engine.firing()), \
                "grad_norm_spike never resolved after the storm"
            assert reg.get_value("alerts_firing",
                                 {"rule": "grad_norm_spike"}) == 0.0
            assert _alert_events(events_dir, "resolved",
                                 "grad_norm_spike")
            # still no loss-based sentinel verdict, storm to resolve
            assert not [e for e in load_events(str(events_dir))
                        if e.get("category") == "sentinel"
                        and e.get("name") == "bad_step"]
        finally:
            stop.set()
            if proc_t.poll() is None:
                proc_t.kill()
                proc_t.wait(timeout=30)
            trainer_log.close()
            hist.flush()

    # -- the postmortem reconstructs the incident offline: lifecycle
    # chain plus the rule's series AND its companions around the window
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "postmortem.py"),
         "--run-dir", str(tmp_path), "--alert", aid],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    text = out.stdout
    assert f"incident {aid}" in text
    assert "alert lifecycle:" in text
    assert "fired" in text and "resolved" in text
    assert "profile_requested" in text
    assert "grad_norm:" in text
    assert "update_ratio:" in text
    assert "before" in text and "during" in text and "after" in text
    assert "journal slice" in text
