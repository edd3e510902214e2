"""Pipeline parallelism over the 'stage' mesh axis (SURVEY §2.3 PP row).

Mirrors torch's pipelining test approach (schedule output == unpipelined
module output): the 4-stage GPipe/1F1B pipeline must reproduce the plain
sequential block stack bit-for-tolerance, forward AND backward, on the fake
8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_train_tpu.config import (
    MeshConfig,
    ModelConfig,
    OptimConfig,
    PrecisionConfig,
)
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
from pytorch_distributed_train_tpu.parallel.partition import rules_for_model

TINY = dict(
    name="llama_pp", vocab_size=64, hidden_size=32, num_layers=4,
    num_heads=4, num_kv_heads=4, mlp_dim=64, max_seq_len=16,
)


def _build(devices8, stage=4, data=2, fsdp=1, microbatches=0, schedule="gpipe"):
    mesh_cfg = MeshConfig(stage=stage, data=data, fsdp=fsdp)
    mesh = build_mesh(mesh_cfg, devices8[: stage * data * fsdp])
    cfg = ModelConfig(**TINY, pipeline_microbatches=microbatches,
                      pipeline_schedule=schedule)
    model = build_model(cfg, PrecisionConfig(), mesh=mesh, mesh_cfg=mesh_cfg)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (8, 16)), jnp.int32
    )
    variables = model.init({"params": jax.random.PRNGKey(0)}, ids)
    return mesh, model, variables, ids


def _reference_logits(model, variables, ids):
    """Unpipelined ground truth: sequential scan over ALL stacked blocks."""
    p = variables["params"]
    x = model.embed.apply({"params": p["tok_embed"]}, ids).astype(model.dtype)

    def body(h, p_one):
        return model.block.apply({"params": p_one}, h), None

    h, _ = jax.lax.scan(body, x, p["blocks"])
    h = model.final_norm.apply({"params": p["final_norm"]}, h)
    return model.lm_head.apply({"params": p["lm_head"]}, h).astype(jnp.float32)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_matches_sequential_forward(devices8, schedule):
    mesh, model, variables, ids = _build(devices8, schedule=schedule)
    with mesh:
        got = jax.jit(lambda v, i: model.apply(v, i, train=False))(variables, ids)
        want = jax.jit(lambda v, i: _reference_logits(model, v, i))(variables, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_pipeline_matches_sequential_backward(devices8):
    mesh, model, variables, ids = _build(devices8, microbatches=8)

    def loss_pp(v):
        return jnp.mean(model.apply(v, ids) ** 2)

    def loss_ref(v):
        return jnp.mean(_reference_logits(model, v, ids) ** 2)

    with mesh:
        l_pp, g_pp = jax.jit(jax.value_and_grad(loss_pp))(variables)
        l_ref, g_ref = jax.jit(jax.value_and_grad(loss_ref))(variables)
    np.testing.assert_allclose(float(l_pp), float(l_ref), atol=1e-6, rtol=1e-6)
    flat_pp = jax.tree_util.tree_leaves_with_path(g_pp)
    flat_ref = {jax.tree_util.keystr(p): g
                for p, g in jax.tree_util.tree_leaves_with_path(g_ref)}
    for path, g in flat_pp:
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_ref[jax.tree_util.keystr(path)]),
            atol=3e-5, rtol=3e-5, err_msg=jax.tree_util.keystr(path),
        )


def test_interleaved_matches_sequential(devices8):
    """Circular schedule (2 stages x 2 chunks over 4 layers): forward AND
    backward must match the plain sequential stack."""
    mesh_cfg = MeshConfig(stage=2, data=2, fsdp=2)
    mesh = build_mesh(mesh_cfg, devices8)
    cfg = ModelConfig(**TINY, pipeline_schedule="interleaved",
                      pipeline_chunks=2, pipeline_microbatches=4)
    model = build_model(cfg, PrecisionConfig(), mesh=mesh, mesh_cfg=mesh_cfg)
    ids = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (8, 16)), jnp.int32
    )
    variables = model.init({"params": jax.random.PRNGKey(0)}, ids)
    # reference: un-interleave (C, S, Lps, ...) → (L, ...) and scan
    p = dict(variables["params"])
    p["blocks"] = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[3:]), p.pop("blocks_csl")
    )
    ref_vars = {"params": p}

    def loss_pp(v):
        return jnp.mean(model.apply(v, ids) ** 2)

    def loss_ref(v):
        return jnp.mean(_reference_logits(model, v, ids) ** 2)

    with mesh:
        l_pp, g_pp = jax.jit(jax.value_and_grad(loss_pp))(variables)
        l_ref, g_ref = jax.jit(jax.value_and_grad(loss_ref))(ref_vars)
    np.testing.assert_allclose(float(l_pp), float(l_ref), atol=1e-6,
                               rtol=1e-6)
    # compare grads: re-interleave the reference's block grads
    g_ref_csl = dict(g_ref["params"])
    g_ref_csl["blocks_csl"] = jax.tree.map(
        lambda a: a.reshape((2, 2, -1) + a.shape[1:]),
        g_ref_csl.pop("blocks"),
    )
    flat_ref = {jax.tree_util.keystr(pth): g for pth, g in
                jax.tree_util.tree_leaves_with_path({"params": g_ref_csl})}
    for pth, g in jax.tree_util.tree_leaves_with_path(g_pp):
        key = jax.tree_util.keystr(pth)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_ref[key]),
            atol=3e-5, rtol=3e-5, err_msg=key,
        )


def test_pipeline_moe_train_step(devices8):
    """MoE inside the pipeline: aux losses escape the manual region and the
    PP x EP composition trains."""
    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.losses import get_loss_fn
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.train_state import TrainState

    mesh_cfg = MeshConfig(stage=2, data=2, expert=2)
    mesh = build_mesh(mesh_cfg, devices8)
    cfg = ModelConfig(**TINY, num_experts=4, expert_top_k=2,
                      pipeline_microbatches=4)
    model = build_model(cfg, PrecisionConfig(), mesh=mesh, mesh_cfg=mesh_cfg)
    tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=1e-2, schedule="constant",
                    warmup_steps=0), total_steps=10,
    )
    rules = rules_for_model("llama_pp")
    ids = jnp.asarray(
        np.random.default_rng(3).integers(0, 64, (8, 16)), jnp.int32
    )

    def init_state(rng):
        v = model.init({"params": rng}, ids)
        return TrainState.create(params=v["params"], tx=tx)

    rng = jax.random.PRNGKey(0)
    sharding = steps_lib.state_shardings(
        mesh, rules, jax.eval_shape(init_state, rng))
    state = jax.jit(init_state, out_shardings=sharding)(rng)
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, get_loss_fn("causal_lm_xent"), tx),
        mesh, sharding,
    )
    losses = []
    for _ in range(4):
        state, metrics = step(state, {"input_ids": ids}, rng)
        losses.append(float(metrics["loss"]))
        assert float(metrics["aux_loss"]) > 0.0
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_pipeline_train_step(devices8):
    """Full jitted train step: PP × DP × FSDP composes, loss decreases."""
    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.losses import get_loss_fn
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.train_state import TrainState

    mesh, model, variables, ids = _build(devices8, stage=2, data=2, fsdp=2)
    tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=1e-2, schedule="constant",
                    warmup_steps=0), total_steps=10,
    )
    rules = rules_for_model("llama_pp")

    def init_state(rng):
        v = model.init({"params": rng}, ids)
        return TrainState.create(params=v["params"], tx=tx)

    rng = jax.random.PRNGKey(0)
    shape = jax.eval_shape(init_state, rng)
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    state = jax.jit(init_state, out_shardings=sharding)(rng)
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, get_loss_fn("causal_lm_xent"), tx),
        mesh, sharding,
    )
    batch = {"input_ids": ids}
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_pipeline_compiles_without_involuntary_remat(devices8, capfd):
    """The PP×DP×FSDP step must compile with no spmd_partitioner
    "Involuntary full rematerialization" diagnostics (an earlier CPU dry
    run carried one — the microbatch reshape left
    batch-sharding on the scanned dim and GSPMD replicated a tensor every
    step as its last-resort cross-dim reshard). The staged gather→slice
    constraints in parallel/pipeline.py::_constrain_microbatch are what
    keep this clean; capfd sees the XLA C++ warning stream."""
    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.losses import get_loss_fn
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.train_state import TrainState

    mesh, model, variables, ids = _build(devices8, stage=2, data=2, fsdp=2)
    tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=1e-2, schedule="constant",
                    warmup_steps=0), total_steps=10,
    )
    rules = rules_for_model("llama_pp")

    def init_state(rng):
        v = model.init({"params": rng}, ids)
        return TrainState.create(params=v["params"], tx=tx)

    rng = jax.random.PRNGKey(0)
    shape = jax.eval_shape(init_state, rng)
    sharding = steps_lib.state_shardings(mesh, rules, shape)
    state = jax.jit(init_state, out_shardings=sharding)(rng)
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, get_loss_fn("causal_lm_xent"), tx),
        mesh, sharding,
    )
    capfd.readouterr()  # drop init-time noise; isolate the step compile
    state, metrics = step(state, {"input_ids": ids}, rng)
    assert np.isfinite(float(metrics["loss"]))
    err = capfd.readouterr().err
    assert "Involuntary full rematerialization" not in err, err[-2000:]


def test_interleaved_dense_packing_multi_group_odd_chunks(devices8):
    """The r4 DENSE schedule packs groups with zero drain; this pins the
    forward at a shape the original test never hits — C=3 chunks (6
    layers over 2 stages) and G=3 groups (M=6 microbatches) — against
    the sequential stack, so the residue/group index arithmetic
    (rho = (t-s) mod S, g = (t-rho)//V, v = (t-rho) mod V) is exercised
    across multiple group boundaries and odd laps."""
    mesh_cfg = MeshConfig(stage=2, data=2, fsdp=2)
    mesh = build_mesh(mesh_cfg, devices8)
    cfg = ModelConfig(**{**TINY, "num_layers": 6},
                      pipeline_schedule="interleaved",
                      pipeline_chunks=3, pipeline_microbatches=6)
    model = build_model(cfg, PrecisionConfig(), mesh=mesh, mesh_cfg=mesh_cfg)
    ids = jnp.asarray(
        np.random.default_rng(9).integers(0, 64, (12, 16)), jnp.int32
    )
    variables = model.init({"params": jax.random.PRNGKey(1)}, ids)
    p = dict(variables["params"])
    p["blocks"] = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[3:]), p.pop("blocks_csl")
    )
    with mesh:
        out_pp = jax.jit(lambda v: model.apply(v, ids))(variables)
        out_ref = jax.jit(
            lambda v: _reference_logits(model, v, ids))({"params": p})
    np.testing.assert_allclose(np.asarray(out_pp), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)
