"""Microbatched (lax.scan) train step + overlapped-collectives tests
(ISSUE 14 tentpole): the scan step must be compatible with the
single-shot step at matched global batch — same params (to reduction-
order rounding), same skip/loss-scale semantics (gated ONCE on the
accumulated grads), donation preserved — and the shard_map overlap path
must match the GSPMD step while emitting per-bucket collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny import TINY

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
from pytorch_distributed_train_tpu.parallel.partition import grad_buckets
from pytorch_distributed_train_tpu.train_state import (
    DynamicScale,
    TrainState,
)

MODEL_CFG = ModelConfig(name="vit_b16", num_classes=10, image_size=8,
                        patch_size=4, hidden_size=32, num_layers=2,
                        num_heads=4, mlp_dim=64, dropout_rate=0.0)
OPT_CFG = OptimConfig(name="adamw", learning_rate=1e-3, schedule="constant",
                      warmup_steps=0, weight_decay=0.01, grad_clip_norm=1.0)


def _batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "image": jnp.asarray(rng.standard_normal((n, 8, 8, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, n), jnp.int32),
    }


@pytest.fixture(scope="module")
def setup(devices8):
    mesh = build_mesh(MeshConfig(data=8), devices8)
    model = build_model(MODEL_CFG, PrecisionConfig())
    loss_fn = get_loss_fn("softmax_xent")
    tx, _ = make_optimizer(OPT_CFG, total_steps=100)
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )

    rules = rules_for_model("vit_b16")

    def init_state(rng, dynamic_scale=False):
        variables = model.init({"params": rng}, jnp.zeros((2, 8, 8, 3)),
                               train=False)
        ds = (DynamicScale.create(2.0**15, 2000)
              if dynamic_scale else None)
        return TrainState.create(params=variables["params"], tx=tx,
                                 dynamic_scale=ds)

    shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    shape_ds = jax.eval_shape(lambda r: init_state(r, True),
                              jax.random.PRNGKey(0))
    sharding_ds = steps_lib.state_shardings(mesh, rules, shape_ds)
    return dict(mesh=mesh, model=model, loss_fn=loss_fn, tx=tx,
                init_state=init_state, shape=shape, sharding=sharding,
                shape_ds=shape_ds, sharding_ds=sharding_ds)


def _fresh(setup, dynamic_scale=False):
    sharding = setup["sharding_ds"] if dynamic_scale else setup["sharding"]
    return jax.jit(
        lambda r: setup["init_state"](r, dynamic_scale),
        out_shardings=sharding)(jax.random.PRNGKey(0))


def _run(setup, n_steps=2, dynamic_scale=False, batches=None, **kw):
    sharding = setup["sharding_ds"] if dynamic_scale else setup["sharding"]
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(setup["model"], setup["loss_fn"],
                                  setup["tx"], **kw),
        setup["mesh"], sharding)
    state = _fresh(setup, dynamic_scale)
    metrics = {}
    for i in range(n_steps):
        b = batches[i] if batches is not None else _batch(seed=i)
        state, metrics = step(state, b, jax.random.PRNGKey(42))
    return state, metrics


def test_microbatched_matches_single_shot(setup):
    """accum=k over the SAME global batch == single-shot, to reduction-
    order rounding (mean of per-microbatch means vs one global mean)."""
    s1, m1 = _run(setup)
    for k in (2, 4):
        sk, mk = _run(setup, grad_accum_steps=k)
        assert abs(float(m1["loss"]) - float(mk["loss"])) < 1e-5
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
            jax.device_get(s1.params), jax.device_get(sk.params))
        # opt_state too — counts AND moments (the schedule/bias-
        # correction counters must advance once per SCAN, not per
        # microbatch: LR semantics of the matched-global-batch step)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5),
            jax.device_get(s1.opt_state), jax.device_get(sk.opt_state))


def test_microbatched_loss_scale_gate_once(setup):
    """One NaN microbatch poisons the ACCUMULATED grads → exactly one
    skipped update: params unchanged, step advances, the dynamic scale
    halves ONCE (GradScaler semantics at the whole-step level)."""
    bad = _batch(seed=0)
    bad["image"] = bad["image"].at[3:5].set(jnp.nan)  # one microbatch slice
    state, metrics = _run(setup, n_steps=1, dynamic_scale=True,
                          batches=[bad], grad_accum_steps=4,
                          numeric_guard=True)
    ref = _fresh(setup, dynamic_scale=True)
    assert int(state.step) == 1
    assert float(metrics["update_skipped"]) == 1.0
    assert float(metrics["grads_finite"]) == 0.0
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        jax.device_get(state.params), jax.device_get(ref.params))
    assert float(state.dynamic_scale.scale) == 2.0**14  # halved once


def test_microbatched_unscaled_guard(setup):
    """numeric_guard without loss scaling: same one-skip semantics."""
    bad = _batch(seed=0)
    bad["image"] = bad["image"].at[0].set(jnp.inf)
    state, metrics = _run(setup, n_steps=1, batches=[bad],
                          grad_accum_steps=2, numeric_guard=True)
    ref = _fresh(setup)
    assert int(state.step) == 1
    assert float(metrics["update_skipped"]) == 1.0
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        jax.device_get(state.params), jax.device_get(ref.params))


def test_microbatched_donation_preserved(setup):
    """Donation must survive the scan restructure: the compiled step
    aliases the donated TrainState into its outputs (AOT
    memory_analysis alias accounting — no new state copy)."""
    batch = {
        "image": jax.ShapeDtypeStruct((32, 8, 8, 3), jnp.float32),
        "label": jax.ShapeDtypeStruct((32,), jnp.int32),
    }
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(setup["shape"]))
    aliases = {}
    for k in (1, 4):
        step = steps_lib.jit_train_step(
            steps_lib.make_train_step(setup["model"], setup["loss_fn"],
                                      setup["tx"], grad_accum_steps=k),
            setup["mesh"], setup["sharding"])
        ma = step.lower(setup["shape"], batch, rng).compile() \
            .memory_analysis()
        aliases[k] = int(ma.alias_size_in_bytes)
    # Donated state aliases in BOTH variants, and the scan version
    # aliases no less than the single-shot one (no new copies). The
    # 8-way sharded per-device aliasing is state_bytes/8 at minimum.
    assert aliases[1] >= state_bytes // 8
    assert aliases[4] >= aliases[1]


def test_grad_accum_must_divide(setup):
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(setup["model"], setup["loss_fn"],
                                  setup["tx"], grad_accum_steps=3),
        setup["mesh"], setup["sharding"])
    with pytest.raises(ValueError, match="does not divide"):
        step(_fresh(setup), _batch(32), jax.random.PRNGKey(0))


def test_grad_buckets_invariants(setup):
    params = setup["shape"].params
    leaves = jax.tree_util.tree_leaves(params)
    buckets = grad_buckets(params, 4 * 1024)
    flat = [i for b in buckets for i in b]
    # every leaf exactly once, in REVERSE parameter order (the order
    # backward produces grads — the DDP reducer's registration order)
    assert flat == list(reversed(range(len(leaves))))
    sizes = [
        sum(int(np.prod(leaves[i].shape)) * leaves[i].dtype.itemsize
            for i in b)
        for b in buckets
    ]
    assert all(s >= 4 * 1024 for s in sizes[:-1])  # all but the tail
    assert len(buckets) > 1
    # one giant bucket when the cap exceeds the model
    assert len(grad_buckets(params, 1 << 40)) == 1
    with pytest.raises(ValueError):
        grad_buckets(params, 0)


def _overlap_step(setup, *, accum, bucketed, bucket_kb=64):
    axes = ("data", "fsdp")
    if bucketed:
        reduce_grads, buckets = steps_lib.overlap_grad_reducer(
            setup["shape"].params, 1, axes)  # 1 MiB cap
        kw = dict(reduce_grads=reduce_grads)
        n_buckets = len(buckets)
    else:
        kw = dict(
            reduce_grads_accum=steps_lib.monolithic_grad_reducer(axes))
        n_buckets = 0
    ts = steps_lib.make_train_step(
        setup["model"], setup["loss_fn"], setup["tx"],
        grad_accum_steps=accum,
        reduce_metrics=steps_lib.metrics_reducer(axes), **kw)
    return steps_lib.jit_overlap_train_step(
        ts, setup["mesh"], setup["sharding"]), n_buckets


def test_overlap_matches_gspmd(setup):
    """The shard_map bucketed step must produce the same training as
    the GSPMD jit step (pmean of per-shard means == global mean)."""
    ostep, _ = _overlap_step(setup, accum=2, bucketed=True)
    state = _fresh(setup)
    for i in range(2):
        state, metrics = ostep(state, _batch(seed=i),
                               jax.random.PRNGKey(42))
    ref, ref_m = _run(setup, grad_accum_steps=2)
    assert abs(float(metrics["loss"]) - float(ref_m["loss"])) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
        jax.device_get(state.params), jax.device_get(ref.params))


def test_overlap_collective_placement(setup):
    """Placement evidence (the tier-1 CPU AOT smoke of the overlap
    A/B): the bucketed arm issues its grad reductions INSIDE the
    accumulation scan — all-reduces in the while-body computation,
    where the latency-hiding scheduler can overlap them with the next
    microbatch — while the monolithic arm reduces the accumulated tree
    once in the entry computation. Post-optimization instruction
    TOTALS can coincide (XLA's combiner normalizes both); placement
    cannot."""
    from tools.aot_ab import _count_collectives

    batch = {
        "image": jax.ShapeDtypeStruct((32, 8, 8, 3), jnp.float32),
        "label": jax.ShapeDtypeStruct((32,), jnp.int32),
    }
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    counts = {}
    for bucketed in (False, True):
        step, n_buckets = _overlap_step(setup, accum=2, bucketed=bucketed)
        txt = step.lower(setup["shape"], batch, rng).compile().as_text()
        counts[bucketed] = _count_collectives(txt)
    assert counts[False]["all_reduce"] > 0
    assert counts[True]["all_reduce"] > 0
    assert counts[True]["all_reduce_in_loop"] > 0, counts
    assert counts[False]["all_reduce_in_loop"] == 0, counts


def test_overlap_refuses_sharded_state(setup, devices8):
    """A TrainState sharded over a batch axis must be refused loudly —
    the replicated-DP contract of the overlap path."""
    mesh = build_mesh(MeshConfig(data=2, fsdp=4), devices8)
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )

    sharding = steps_lib.state_shardings(
        mesh, rules_for_model("vit_b16"), setup["shape"])
    ts = steps_lib.make_train_step(setup["model"], setup["loss_fn"],
                                   setup["tx"])
    with pytest.raises(ValueError, match="replicated"):
        steps_lib.jit_overlap_train_step(ts, mesh, sharding)


def _one_leaf_sharded(sharding, mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    leaves, treedef = jax.tree_util.tree_flatten(sharding.params)
    leaves[0] = NamedSharding(mesh, PartitionSpec("data"))
    return sharding.replace(
        params=jax.tree_util.tree_unflatten(treedef, leaves))


# (id, mesh, layout, mode): a leaf is reduced once (per_leaf) where every
# device lies on a batch axis, there is more than one, and the whole
# state is replicated; the partitioner's once-a-use everywhere else.
PLAN_CASES = [
    ("data8-replicated", dict(data=8), "rules", "per_leaf"),
    ("data4-replicated", dict(data=4), "rules", "per_leaf"),
    ("data2-fsdp2-all-replicated", dict(data=2, fsdp=2), "replicated",
     "per_leaf"),
    ("one-device", dict(data=1), "rules", "per_use"),
    ("data8-one-sharded-leaf", dict(data=8), "one_sharded", "per_use"),
    ("data2-fsdp4-zero3", dict(data=2, fsdp=4), "rules", "per_use"),
    ("data2-fsdp4-zero1", dict(data=2, fsdp=4), "zero1", "per_use"),
    ("data4-tensor2-all-replicated", dict(data=4, tensor=2), "replicated",
     "per_use"),
    ("data4-context2", dict(data=4, context=2), "rules", "per_use"),
]


@pytest.mark.parametrize("mesh_kw,layout,mode",
                         [c[1:] for c in PLAN_CASES],
                         ids=[c[0] for c in PLAN_CASES])
def test_grad_reduce_plan_reads_the_layout(setup, devices8, mesh_kw,
                                           layout, mode):
    from jax.sharding import NamedSharding, PartitionSpec

    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )

    n = int(np.prod(list(mesh_kw.values())))
    mesh = build_mesh(MeshConfig(**mesh_kw), devices8[:n])
    if layout == "replicated":
        sharding = jax.tree.map(
            lambda _: NamedSharding(mesh, PartitionSpec()), setup["shape"])
    else:
        sharding = steps_lib.state_shardings(
            mesh, rules_for_model("vit_b16"), setup["shape"],
            zero_stage=1 if layout == "zero1" else 3)
        if layout == "one_sharded":
            sharding = _one_leaf_sharded(sharding, mesh)
    plan = steps_lib.grad_reduce_plan(mesh, sharding)
    assert plan.mode == mode, plan
    assert plan.batch_devices == int(
        np.prod([mesh_kw.get(a, 1) for a in ("data", "fsdp")]))


def test_jit_train_step_takes_no_compile_options(setup, monkeypatch):
    """Whatever the plan says, on one chip, on the CPU and under any
    layout the step is jitted as before: the asynchronous-collective
    options PR 27 tried there measured slower on the chip and are not
    passed (PERF.md section 6)."""
    seen = {}
    monkeypatch.setattr(jax, "jit",
                        lambda fn, **kw: seen.update(kw) or fn)
    steps_lib.jit_train_step(lambda *a: a, setup["mesh"], setup["sharding"])
    assert sorted(seen) == ["donate_argnums", "in_shardings",
                            "out_shardings"]


def test_trainer_validates_compute_knobs(tmp_path):
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.trainer import Trainer

    def cfg_with(overrides):
        cfg = get_preset("resnet18_cifar10")
        cfg.data.dataset = "synthetic_images"
        cfg.data.synthetic_size = 64
        cfg.data.batch_size = 16
        cfg.checkpoint.dir = str(tmp_path / "ckpt")
        cfg.checkpoint.resume = "none"
        cfg.obs.events = False
        cfg.apply_overrides(overrides)
        return cfg

    with pytest.raises(ValueError, match="accum"):
        Trainer(cfg_with(["train.grad_accum_steps=2",
                          "optim.accum_steps=2"]))
    with pytest.raises(ValueError, match="divide"):
        Trainer(cfg_with(["train.grad_accum_steps=3"]))
    with pytest.raises(ValueError, match="fused_epilogue"):
        # lamb has no fused epilogue — refused loudly, never silent
        Trainer(cfg_with(["train.fused_epilogue=true",
                          "optim.name=lamb"]))
    with pytest.raises(ValueError, match="EMA"):
        Trainer(cfg_with(["train.fused_epilogue=true",
                          "optim.ema_decay=0.99"]))


def test_latency_hiding_flag_preset():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    assert steps_lib.ensure_latency_hiding_flags(env)
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in \
        env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert not steps_lib.ensure_latency_hiding_flags(env)  # idempotent


def test_microbatched_resume_exact(tmp_path):
    """Acceptance pin: the microbatched step composes with checkpoint
    resume — save-at-2/restore/continue-to-4 equals an uninterrupted
    4-step run (same TrainState contract, same per-step PRNG folds,
    same mid-epoch batch fast-forward)."""
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.trainer import Trainer

    def cfg_for(d):
        cfg = get_preset("resnet18_cifar10")
        cfg.apply_overrides([
            *TINY, "data.synthetic_size=64", "epochs=0", "total_steps=4",
            f"checkpoint.dir={d}", "checkpoint.save_every_steps=2",
            "checkpoint.best_metric=", "obs.events=false",
            "train.grad_accum_steps=2"])
        return cfg

    t1 = Trainer(cfg_for(tmp_path / "straight"))
    straight = t1.fit()
    t1.close()

    t2 = Trainer(cfg_for(tmp_path / "resumed"))
    t2.fit(max_steps=2)
    t2.close()
    t3 = Trainer(cfg_for(tmp_path / "resumed"))
    assert t3.resumed and int(t3.state.step) == 2
    resumed = t3.fit()
    t3.close()

    assert int(straight.step) == int(resumed.step) == 4
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        jax.device_get(straight.params), jax.device_get(resumed.params))


def test_overlap_per_shard_rng_distinct(setup):
    """The replicated rng is re-keyed per shard inside the shard_map
    body (steps.shard_rng_fold) — without it every replica would draw
    the SAME dropout/augment randomness for its local batch (DDP wants
    per-rank independent draws)."""
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_train_tpu.utils.compat import shard_map

    mesh = setup["mesh"]
    probe = shard_map(
        lambda r: steps_lib.shard_rng_fold(r, ("data", "fsdp"))[None],
        mesh=mesh, in_specs=(P(),), out_specs=P("data"),
        check_vma=False)
    with mesh:
        keys = np.asarray(jax.jit(probe)(jax.random.PRNGKey(7)))
    assert keys.shape[0] == 8
    assert len({tuple(k) for k in keys}) == 8  # all shards distinct
