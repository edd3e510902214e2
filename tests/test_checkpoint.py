"""Checkpoint save/restore tests (SURVEY §4.4, §5.4): bitwise round-trip,
auto-resume, reshard-on-restore (save on one mesh layout, restore on
another — the FSDP→GSPMD requirement of BASELINE.json:11)."""

import functools

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from tiny import tiny_cfg

from pytorch_distributed_train_tpu import steps as steps_lib
from pytorch_distributed_train_tpu.checkpoint import CheckpointManager
from pytorch_distributed_train_tpu.config import (
    CheckpointConfig,
    MeshConfig,
    ModelConfig,
    OptimConfig,
    PrecisionConfig,
)
from pytorch_distributed_train_tpu.losses import get_loss_fn
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.optim import make_optimizer
from pytorch_distributed_train_tpu.parallel.mesh import MESH_AXES, build_mesh
from pytorch_distributed_train_tpu.parallel.partition import rules_for_model
from pytorch_distributed_train_tpu.train_state import TrainState


@functools.cache
def _programs(mesh):
    """The model, its sharded init and its jitted step on ``mesh``,
    compiled once a worker; the step donates its state, so ``_build``
    hands every test a fresh one."""
    model_cfg = ModelConfig(name="resnet18", num_classes=10, image_size=8)
    model = build_model(model_cfg, PrecisionConfig())
    tx, _ = make_optimizer(
        OptimConfig(name="momentum", learning_rate=0.1, schedule="constant",
                    warmup_steps=0), total_steps=100,
    )
    rules = rules_for_model(model_cfg.name)

    def init_state(rng):
        x = jnp.zeros((2, model_cfg.image_size, model_cfg.image_size, 3))
        variables = model.init({"params": rng}, x, train=False)
        return TrainState.create(params=variables["params"], tx=tx,
                                 batch_stats=variables.get("batch_stats", {}))

    shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, get_loss_fn("softmax_xent"), tx),
        mesh, sharding,
    )
    return (model, jax.jit(init_state, out_shardings=sharding), step, shape,
            sharding)


def _build(mesh):
    model, init, step, shape, sharding = _programs(mesh)
    return model, init(jax.random.PRNGKey(0)), step, shape, sharding


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "image": jnp.asarray(rng.standard_normal((8, 8, 8, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, 8), jnp.int32),
    }


def _abstract(shape, sharding):
    """Abstract TrainState (ShapeDtypeStruct + sharding) for restore."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shape, sharding,
    )


def test_roundtrip_bitwise(tmp_ckpt_dir, devices8):
    mesh = build_mesh(MeshConfig(data=8, fsdp=1, tensor=1, context=1), devices8)
    model, state, step, shape, sharding = _build(mesh)
    rng = jax.random.PRNGKey(1)
    for i in range(3):
        state, _ = step(state, _batch(i), rng)

    ck = CheckpointManager(CheckpointConfig(dir=tmp_ckpt_dir, save_every_steps=1,
                                            async_save=False))
    assert ck.save(state, epoch=1)
    ck.wait()
    assert ck.latest_step() == 3

    restored, meta = ck.restore(_abstract(shape, sharding))
    assert int(restored.step) == 3
    assert meta["epoch"] == 1
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(state.params), jax.device_get(restored.params),
    )
    # optimizer momentum restored bitwise too
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(state.opt_state), jax.device_get(restored.opt_state),
    )
    ck.close()


def test_reshard_on_restore(tmp_ckpt_dir, devices8):
    """Save with DP layout (8,1), restore into FSDP layout (2,4) — the mesh
    changed between save and resume (SURVEY §5.4 'reshard-on-restore')."""
    mesh_dp = build_mesh(MeshConfig(data=8, fsdp=1, tensor=1, context=1), devices8)
    _, state, step, _, _ = _build(mesh_dp)
    rng = jax.random.PRNGKey(1)
    state, _ = step(state, _batch(0), rng)
    ck = CheckpointManager(CheckpointConfig(dir=tmp_ckpt_dir, async_save=False))
    ck.save(state, epoch=0)
    ck.wait()

    mesh_fsdp = build_mesh(MeshConfig(data=2, fsdp=4, tensor=1, context=1), devices8)
    _, _, step2, shape2, sharding2 = _build(mesh_fsdp)
    restored, _ = ck.restore(_abstract(shape2, sharding2))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        jax.device_get(state.params), jax.device_get(restored.params),
    )
    # restored state steps fine on the new mesh
    next_state, metrics = step2(restored, _batch(1), rng)
    assert np.isfinite(float(metrics["loss"]))
    ck.close()


def test_resume_continues_identically(tmp_ckpt_dir, devices8):
    """Train 2 steps, checkpoint, train 2 more; vs restore + 2 steps — same
    params (the kill-and-resume contract, SURVEY §5.3c)."""
    mesh = build_mesh(MeshConfig(data=8, fsdp=1, tensor=1, context=1), devices8)
    _, state, step, shape, sharding = _build(mesh)
    rng = jax.random.PRNGKey(1)
    for i in range(2):
        state, _ = step(state, _batch(i), rng)
    ck = CheckpointManager(CheckpointConfig(dir=tmp_ckpt_dir, async_save=False))
    ck.save(state)
    ck.wait()
    cont = state
    for i in range(2, 4):
        cont, _ = step(cont, _batch(i), rng)

    restored, _ = ck.restore(_abstract(shape, sharding))
    for i in range(2, 4):
        restored, _ = step(restored, _batch(i), rng)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                atol=1e-6),
        jax.device_get(cont.params), jax.device_get(restored.params),
    )
    ck.close()


def test_best_checkpoint_tracker(tmp_path, devices8):
    """`model_best.pth` semantics: <dir>/best holds the step whose eval
    metric was best, the watermark survives a restart, and a non-improving
    eval does not overwrite it."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    def make_cfg():
        return tiny_cfg(
            "data.synthetic_size=128", "data.batch_size=32", "total_steps=4",
            "eval_every_steps=2", f"checkpoint.dir={tmp_path}/ckpt",
            "checkpoint.save_every_steps=2",
            "checkpoint.best_metric=accuracy", "obs.log_every_steps=100")

    t = Trainer(make_cfg())
    t.fit()
    t.close()
    best_dir = tmp_path / "ckpt" / "best"
    assert best_dir.exists()
    from pytorch_distributed_train_tpu.checkpoint import (
        BestCheckpointTracker,
    )

    # A fresh tracker over the same dir recovers the watermark from meta.
    tracker = BestCheckpointTracker(make_cfg().checkpoint)
    assert tracker.best_value is not None
    best_before = tracker.best_value
    best_step_before = tracker.mgr.latest_step()
    assert best_step_before is not None

    # Non-improving update must be a no-op; improving one must save.
    class _S:  # minimal stand-in accepted by _savable
        step = 99
        params = {"w": jnp.zeros((2,))}
        opt_state = {}
        batch_stats = {}
        ema_params = None
        dynamic_scale = None

    worse = {"accuracy": best_before - 1.0, "loss": 0.0}
    assert tracker.update(worse, _S(), epoch=0, step=99) is False
    assert tracker.mgr.latest_step() == best_step_before
    better = {"accuracy": best_before + 1.0, "loss": 0.0}
    assert tracker.update(better, _S(), epoch=0, step=99) is True
    tracker.mgr.wait()
    assert tracker.mgr.latest_step() == 99
    assert tracker.best_value == better["accuracy"]
    tracker.close()

    # Typo'd metric name fails loudly.
    tracker2 = BestCheckpointTracker(make_cfg().checkpoint)
    with pytest.raises(KeyError, match="best_metric"):
        tracker2.update({"loss": 1.0}, _S(), epoch=0, step=100)
    tracker2.close()

    # Reconfigured metric/mode must NOT inherit the stale watermark (an
    # old accuracy=0.93 would make every loss "worse" forever).
    import dataclasses as dc

    recfg = dc.replace(make_cfg().checkpoint, best_metric="loss",
                       best_mode="min")
    tracker3 = BestCheckpointTracker(recfg)
    assert tracker3.best_value is None
    tracker3.close()

    # resume="none" is a fresh run: a reused dir must not pin the old
    # run's watermark (its stale best would never be beaten early on).
    fresh = dc.replace(make_cfg().checkpoint, resume="none")
    tracker4 = BestCheckpointTracker(fresh)
    assert tracker4.best_value is None
    tracker4.close()
