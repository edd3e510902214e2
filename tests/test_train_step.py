"""Distributed train-step parity tests (SURVEY §4.2) — the JAX analogue of
torch's DDP-parity-vs-single-process golden tests
(torch:testing/_internal/distributed/distributed_test.py):

- DP over 8 fake devices must produce the SAME updated params as 1 device
  (DDP semantics: grad all-reduce ≡ big-batch gradient).
- FSDP (params sharded) must produce the same loss/params as DP (sharding is
  layout, not math).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny import TINY
from jax.sharding import Mesh

from pytorch_distributed_train_tpu import steps as steps_lib
from pytorch_distributed_train_tpu.config import (
    MeshConfig,
    ModelConfig,
    OptimConfig,
    PrecisionConfig,
)
from pytorch_distributed_train_tpu.losses import get_loss_fn
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.optim import make_optimizer
from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
from pytorch_distributed_train_tpu.parallel.partition import rules_for_model
from pytorch_distributed_train_tpu.train_state import TrainState


def _make_batch(n=16, image=8, classes=10, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "image": jnp.asarray(rng.standard_normal((n, image, image, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, classes, n), jnp.int32),
    }


def _setup(mesh, model_cfg, opt_cfg, batch_axes=("data", "fsdp")):
    model = build_model(model_cfg, PrecisionConfig())
    loss_fn = get_loss_fn("softmax_xent")
    tx, _ = make_optimizer(opt_cfg, total_steps=100)
    rules = rules_for_model(model_cfg.name)

    def init_state(rng):
        x = jnp.zeros((2, model_cfg.image_size, model_cfg.image_size, 3))
        variables = model.init({"params": rng}, x, train=False)
        return TrainState.create(
            params=variables["params"], tx=tx,
            batch_stats=variables.get("batch_stats", {}),
        )

    rng = jax.random.PRNGKey(0)
    shape = jax.eval_shape(init_state, rng)
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    state = jax.jit(init_state, out_shardings=sharding)(rng)
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, loss_fn, tx), mesh, sharding, batch_axes
    )
    return state, step


def _run_steps(mesh_axes, devices, n_steps=3, model_name="resnet18"):
    # Keyword axis sizes, NOT positional: MESH_AXES gains axes over time
    # (stage was prepended for PP) and a zip would silently re-key.
    mesh_cfg = MeshConfig(**{"data": 1, **mesh_axes})
    mesh = build_mesh(mesh_cfg, devices)
    model_cfg = ModelConfig(name=model_name, num_classes=10, image_size=8)
    opt_cfg = OptimConfig(name="momentum", learning_rate=0.1, schedule="constant",
                          warmup_steps=0, weight_decay=1e-4)
    state, step = _setup(mesh, model_cfg, opt_cfg)
    rng = jax.random.PRNGKey(42)
    losses = []
    for i in range(n_steps):
        batch = _make_batch(seed=i)
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    params = jax.device_get(state.params)
    return losses, params


@pytest.fixture(scope="module")
def single_device_run():
    return _run_steps({}, jax.devices("cpu")[:1])


def test_dp8_matches_single_device(devices8, single_device_run):
    losses1, params1 = single_device_run
    losses8, params8 = _run_steps({"data": 8}, devices8)
    np.testing.assert_allclose(losses1, losses8, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), params1, params8
    )


def test_fsdp_matches_dp(devices8, single_device_run):
    losses1, params1 = single_device_run
    losses_f, params_f = _run_steps({"data": 2, "fsdp": 4}, devices8)
    np.testing.assert_allclose(losses1, losses_f, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), params1, params_f
    )


def test_tensor_parallel_llama_matches_replicated(devices8):
    """TP sharding of a tiny Llama must not change the math."""
    model_cfg = ModelConfig(name="llama", vocab_size=64, hidden_size=32,
                            num_layers=2, num_heads=4, num_kv_heads=4, mlp_dim=64,
                            max_seq_len=16, remat=False)
    opt_cfg = OptimConfig(name="adamw", learning_rate=1e-3, schedule="constant",
                          warmup_steps=0, weight_decay=0.0)
    loss_fn = get_loss_fn("causal_lm_xent")

    def run(mesh_axes, devs):
        mesh_cfg = MeshConfig(**{"data": 1, **mesh_axes})
        mesh = build_mesh(mesh_cfg, devs)
        model = build_model(model_cfg, PrecisionConfig())
        tx, _ = make_optimizer(opt_cfg, total_steps=10)
        rules = rules_for_model("llama")

        def init_state(rng):
            ids = jnp.zeros((2, 16), jnp.int32)
            variables = model.init({"params": rng}, ids, train=False)
            return TrainState.create(params=variables["params"], tx=tx)

        rng = jax.random.PRNGKey(0)
        shape = jax.eval_shape(init_state, rng)
        sharding = steps_lib.state_shardings(mesh, rules, shape)
        state = jax.jit(init_state, out_shardings=sharding)(rng)
        step = steps_lib.jit_train_step(
            steps_lib.make_train_step(model, loss_fn, tx), mesh, sharding
        )
        ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (8, 16)), jnp.int32)
        state, metrics = step(state, {"input_ids": ids}, rng)
        return float(metrics["loss"]), jax.device_get(state.params)

    loss1, params1 = run({}, jax.devices("cpu")[:1])
    loss_tp, params_tp = run({"data": 2, "fsdp": 2, "tensor": 2}, devices8)
    assert abs(loss1 - loss_tp) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-4), params1, params_tp
    )


def test_grad_accumulation_equals_big_batch(devices8):
    """optax.MultiSteps over k micro-batches == one k·B batch step — the
    DDP no_sync() contract (SURVEY C6). Uses a BN-free model: under
    BatchNorm, micro-batch ≠ big-batch normalization in ANY framework."""
    mesh = build_mesh(MeshConfig(data=8, fsdp=1, tensor=1, context=1), devices8)
    model_cfg = ModelConfig(name="vit_b16", num_classes=10, image_size=8,
                            patch_size=4, hidden_size=32, num_layers=2,
                            num_heads=4, mlp_dim=64, dropout_rate=0.0)
    big = _make_batch(n=32, seed=7)

    def run(accum, batches):
        opt_cfg = OptimConfig(name="sgd", learning_rate=0.1, momentum=0.0,
                              schedule="constant", warmup_steps=0,
                              weight_decay=0.0, accum_steps=accum)
        state, step = _setup(mesh, model_cfg, opt_cfg)
        rng = jax.random.PRNGKey(0)
        for b in batches:
            state, _ = step(state, b, rng)
        return jax.device_get(state.params)

    micro = [
        {k: v[i * 8 : (i + 1) * 8] for k, v in big.items()} for i in range(4)
    ]
    p_accum = run(4, micro)
    p_big = run(1, [big])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), p_accum, p_big
    )


def test_offload_state_shardings_metadata(devices8):
    """offload_state_shardings moves ONLY the opt_state subtree to
    pinned_host, preserving every partition spec. (Execution is TPU-only —
    the CPU backend has no annotate_device_placement — so CPU tests cover
    the metadata transform and the trainer's backend gate.)"""
    mesh = build_mesh(MeshConfig(data=4, fsdp=2))
    model_cfg = ModelConfig(name="resnet18", num_classes=10, image_size=8)
    model = build_model(model_cfg, PrecisionConfig())
    tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=0.1, schedule="constant"),
        total_steps=10)
    rules = rules_for_model("resnet18")

    def init_state(rng):
        x = jnp.zeros((2, 8, 8, 3))
        variables = model.init({"params": rng}, x, train=False)
        return TrainState.create(params=variables["params"], tx=tx,
                                 batch_stats=variables.get("batch_stats", {}))

    shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    off = steps_lib.offload_state_shardings(sharding)
    for a, b in zip(jax.tree.leaves(sharding.opt_state),
                    jax.tree.leaves(off.opt_state)):
        assert b.memory_kind == "pinned_host"
        assert a.spec == b.spec and a.mesh == b.mesh
    # params/batch_stats untouched (same objects or same default memory)
    for a, b in zip(jax.tree.leaves(sharding.params),
                    jax.tree.leaves(off.params)):
        assert b.memory_kind != "pinned_host"


def test_trainer_rejects_offload_on_cpu(tmp_path):
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = get_preset("resnet18_cifar10")
    cfg.apply_overrides([*TINY, "data.synthetic_size=64"])
    cfg.optim.offload_state = True
    cfg.checkpoint.dir = str(tmp_path / "ckpt")
    cfg.checkpoint.resume = "none"
    with pytest.raises(ValueError, match="offload_state"):
        Trainer(cfg)


def test_module_grad_norm_metrics(devices8):
    mesh = build_mesh(MeshConfig(data=8))
    model_cfg = ModelConfig(name="resnet18", num_classes=10, image_size=8)
    model = build_model(model_cfg, PrecisionConfig())
    from pytorch_distributed_train_tpu.losses import get_loss_fn as glf

    tx, _ = make_optimizer(OptimConfig(name="momentum", learning_rate=0.1,
                                       schedule="constant"), total_steps=10)
    rules = rules_for_model("resnet18")

    def init_state(rng):
        variables = model.init({"params": rng}, jnp.zeros((2, 8, 8, 3)),
                               train=False)
        return TrainState.create(params=variables["params"], tx=tx,
                                 batch_stats=variables.get("batch_stats", {}))

    shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    state = jax.jit(init_state, out_shardings=sharding)(jax.random.PRNGKey(0))
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, glf("softmax_xent"), tx,
                                  module_grad_norms=True),
        mesh, sharding)
    state, metrics = step(state, _make_batch(), jax.random.PRNGKey(1))
    per_module = {k: float(v) for k, v in metrics.items()
                  if k.startswith("grad_norm/")}
    assert "grad_norm/conv_stem" in per_module
    assert any(k.startswith("grad_norm/stage") for k in per_module)
    assert all(np.isfinite(v) and v >= 0 for v in per_module.values())
    # per-module norms compose to the global norm
    total = float(metrics["grad_norm"])
    rss = float(np.sqrt(sum(v**2 for v in per_module.values())))
    np.testing.assert_allclose(rss, total, rtol=1e-4)


def test_zero1_matches_full_shard(devices8):
    """mesh.zero_stage=1 (ZeRO-1: optimizer-state-only sharding) must be
    pure layout: identical updated params to FULL_SHARD after two steps,
    with params replicated over 'fsdp' and adam moments still sharded."""
    from pytorch_distributed_train_tpu.config import ModelConfig, OptimConfig

    model_cfg = ModelConfig(
        name="llama", vocab_size=64, hidden_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, mlp_dim=64, max_seq_len=16)
    model = build_model(model_cfg, PrecisionConfig())
    loss_fn = get_loss_fn("causal_lm_xent")
    tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=1e-2, schedule="constant",
                    warmup_steps=0), total_steps=100)
    rules = rules_for_model("llama")
    mesh = build_mesh(MeshConfig(data=2, fsdp=4), devices8)
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (16, 16)), jnp.int32)}

    def init_state(rng):
        ids = jnp.zeros((2, 16), jnp.int32)
        variables = model.init({"params": rng}, ids, train=False)
        # ema=True: the EMA mirror must follow the params' replicated
        # layout under zero_stage=1 (eval serves from it)
        return TrainState.create(params=variables["params"], tx=tx,
                                 ema=True)

    results = {}
    for stage in (3, 1):
        shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        sharding = steps_lib.state_shardings(mesh, rules, shape,
                                             zero_stage=stage)
        state = jax.jit(init_state, out_shardings=sharding)(
            jax.random.PRNGKey(0))
        step = steps_lib.jit_train_step(
            steps_lib.make_train_step(model, loss_fn, tx, ema_decay=0.5),
            mesh, sharding)
        for _ in range(2):
            state, metrics = step(state, batch, jax.random.PRNGKey(1))
        results[stage] = (jax.device_get(state.params), sharding,
                          jax.device_get(state.ema_params))

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-6),
        results[1][0], results[3][0])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-6),
        results[1][2], results[3][2])

    z1 = results[1][1]
    flat_p = jax.tree_util.tree_leaves(z1.params)
    assert all("fsdp" not in str(s.spec) for s in flat_p)
    assert all("fsdp" not in str(s.spec)
               for s in jax.tree_util.tree_leaves(z1.ema_params))
    moment_specs = [str(s.spec) for s in
                    jax.tree_util.tree_leaves(z1.opt_state)
                    if hasattr(s, "spec")]
    assert any("fsdp" in sp for sp in moment_specs), moment_specs
