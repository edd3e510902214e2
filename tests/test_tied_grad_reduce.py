"""The tied embedding's gradient is all-reduced ONCE in pure data
parallelism (models/gpt2.py `_per_shard_table`, steps.grad_reduce_plan):
the lookup and the head read a per-shard view of the table, their two
gradient contributions meet as per-shard partial sums, and the sum across
shards comes after. Held here on the CPU mesh: the values are the plain
model's, the compiled step moves the table across devices once instead of
twice, and every case the view does not cover falls back to the plain
path."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from pytorch_distributed_train_tpu.config import (
    MeshConfig,
    ModelConfig,
    PrecisionConfig,
)
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.parallel.mesh import build_mesh

V, C, S = 256, 32, 16
MODEL = ModelConfig(name="gpt2", vocab_size=V, hidden_size=C, num_layers=1,
                    num_heads=2, mlp_dim=64, max_seq_len=S, dropout_rate=0.0)
F32 = PrecisionConfig(compute_dtype="float32")


def _ids(batch):
    return jnp.asarray(
        np.random.default_rng(0).integers(0, V, (batch, S)), jnp.int32)


def _loss(model):
    def f(params, ids):
        logits = model.apply({"params": params}, ids, train=True)
        logp = jax.nn.log_softmax(logits[:, :-1])
        return -jnp.take_along_axis(
            logp, ids[:, 1:, None], axis=-1).mean()
    return f


def _grad_fn(model, mesh):
    rep = NamedSharding(mesh, P())
    return jax.jit(jax.value_and_grad(_loss(model)),
                   in_shardings=(rep, NamedSharding(mesh, P(("data",)))),
                   out_shardings=rep)


@pytest.fixture(scope="module")
def plain(devices8):
    mesh = build_mesh(MeshConfig(data=8), devices8)
    model = build_model(MODEL, F32, mesh=mesh, mesh_cfg=MeshConfig(data=8))
    params = model.init({"params": jax.random.PRNGKey(0)}, _ids(2),
                        train=False)["params"]
    return model, params


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_values_are_the_plain_models(devices8, plain, shards):
    _, params = plain
    mesh_cfg = MeshConfig(data=shards)
    mesh = build_mesh(mesh_cfg, devices8[:shards])
    model = build_model(MODEL, F32, mesh=mesh, mesh_cfg=mesh_cfg)
    assert model.tied_shards == 1  # only the trainer's plan sets it
    ids = _ids(16)
    want_loss, want = _grad_fn(model, mesh)(params, ids)
    got_loss, got = _grad_fn(model.clone(tied_shards=shards), mesh)(
        params, ids)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(
            b, a, rtol=2e-4, atol=1e-7, err_msg=jax.tree_util.keystr(path))


def _table_all_reduces(model, mesh, params, ids) -> int:
    text = _grad_fn(model, mesh).lower(params, ids).compile().as_text()
    return sum(len(re.findall(rf"f32\[{V},{C}\]", m.group(1)))
               for m in re.finditer(r"= ([^\n]*?) all-reduce\(", text))


def test_the_table_crosses_the_devices_once_not_twice(devices8, plain):
    model, params = plain
    mesh = build_mesh(MeshConfig(data=8), devices8)
    ids = _ids(16)
    assert _table_all_reduces(model, mesh, params, ids) == 2
    assert _table_all_reduces(model.clone(tied_shards=8), mesh, params,
                              ids) == 1


def test_the_view_survives_the_heads_kernels(devices8, monkeypatch):
    """The training step's head and loss in the kernels of
    ops/lm_head_loss.py (a device on its own sequences and its own view,
    inside a shard_map over the batch axes; the interpreter here): the
    values are the logits path's, and the table still crosses the devices
    once: the view's gradient leaves the region as per-shard partial
    sums."""
    from pytorch_distributed_train_tpu.losses import causal_lm_xent
    from pytorch_distributed_train_tpu.ops import attention, lm_head

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(lm_head, "_interpret", lambda: True)
    wide = ModelConfig(name="gpt2", vocab_size=V, hidden_size=128,
                       num_layers=1, num_heads=2, mlp_dim=64, max_seq_len=S,
                       dropout_rate=0.0)
    mesh_cfg = MeshConfig(data=8)
    mesh = build_mesh(mesh_cfg, devices8)
    model = build_model(wide, F32, mesh=mesh, mesh_cfg=mesh_cfg).clone(
        tied_shards=8)
    ids = _ids(64)  # 8 sequences of 16 a device: 128 rows a kernel call
    params = model.init({"params": jax.random.PRNGKey(0)}, ids[:2],
                        train=False)["params"]

    def loss_of(m):
        def loss(p, ids):
            out = m.apply({"params": p}, ids, train=True)
            return causal_lm_xent(out, {"input_ids": ids})[0]
        return loss

    def grad_fn(m):
        rep = NamedSharding(mesh, P())
        return jax.jit(jax.value_and_grad(loss_of(m)), in_shardings=(
            rep, NamedSharding(mesh, P(("data",)))), out_shardings=rep)

    want_loss, want = grad_fn(model)(params, ids)
    asked = model.clone(head_operands=True)
    assert "pallas_call" in str(jax.make_jaxpr(loss_of(asked))(params, ids))
    fused = grad_fn(asked)
    lowered = fused.lower(params, ids)
    got_loss, got = fused(params, ids)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(
            b, a, rtol=2e-4, atol=1e-7, err_msg=jax.tree_util.keystr(path))
    text = lowered.compile().as_text()
    assert sum(len(re.findall(rf"f32\[{V},128\]", m.group(1)))
               for m in re.finditer(r"= ([^\n]*?) all-reduce\(", text)) == 1


FALLBACKS = [
    ("batch-does-not-divide", dict(tied_shards=8), 4, True),
    ("fused-head-and-loss", dict(tied_shards=8, fused_loss=True), 16, True),
    ("no-mesh", dict(tied_shards=8), 16, False),
]


@pytest.mark.parametrize("fields,batch,with_mesh",
                         [c[1:] for c in FALLBACKS],
                         ids=[c[0] for c in FALLBACKS])
def test_what_the_view_does_not_cover_takes_the_plain_path(
        devices8, plain, fields, batch, with_mesh):
    """No (shards, V, C) array appears in the traced program."""
    _, params = plain
    mesh_cfg = MeshConfig(data=8)
    kw = (dict(mesh=build_mesh(mesh_cfg, devices8), mesh_cfg=mesh_cfg)
          if with_mesh else {})
    model = build_model(MODEL, F32, **kw).clone(**fields)
    ids = _ids(batch)
    jaxpr = str(jax.make_jaxpr(
        lambda p: model.apply({"params": p}, ids, train=True))(params))
    assert f"[8,{V},{C}]" not in jaxpr


def test_the_trainer_sets_it_from_the_plan(tmp_path, capfd):
    """Eight CPU devices on the data axis, the state replicated: the plan
    is per_leaf, the model is told the eight ways, one line says so."""
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = get_preset("gpt2_small")
    cfg.apply_overrides([
        "model.hidden_size=32", "model.num_layers=1", "model.num_heads=2",
        "model.mlp_dim=64", "model.vocab_size=128", "model.max_seq_len=32",
        "model.dropout_rate=0.0", "data.seq_len=32",
        "data.dataset=synthetic_lm", "data.batch_size=8",
        "data.synthetic_size=16", "total_steps=1",
        "checkpoint.save_every_steps=0", "checkpoint.resume=none",
        f"checkpoint.dir={tmp_path}"])
    trainer = Trainer(cfg)
    try:
        n = len(jax.devices())
        assert trainer.grad_reduce.mode == "per_leaf"
        assert trainer.grad_reduce.batch_devices == n
        assert trainer.model.tied_shards == n
        assert (f"[parallel] grad all-reduce: per_leaf ({n} device(s) on "
                "dataxfsdp;") in capfd.readouterr().out
    finally:
        trainer.close()
