"""LoRA fine-tuning (lora.py): identity at init, frozen base under
training, adapter-only optimizer state, merged export, and the
warm-start-from-base-checkpoint workflow end to end.

The torch analogue of these guarantees lives in the PEFT ecosystem
(requires_grad=False base + nn.Linear adapter merge); here they are
properties of a pure param-tree transform, so each is checked as tree
algebra on real model params rather than module introspection.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_train_tpu import lora as lora_lib
from pytorch_distributed_train_tpu import steps as steps_lib
from pytorch_distributed_train_tpu.config import (
    LoraConfig,
    ModelConfig,
    OptimConfig,
    PrecisionConfig,
    TrainConfig,
)
from pytorch_distributed_train_tpu.losses import get_loss_fn
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.optim import make_optimizer
from pytorch_distributed_train_tpu.steps import apply_model
from pytorch_distributed_train_tpu.train_state import TrainState


def _tiny_llama():
    return ModelConfig(
        name="llama", vocab_size=128, hidden_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, mlp_dim=64, max_seq_len=32,
        dropout_rate=0.0)


def _batch(b=4, s=16, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": jnp.asarray(
        rng.integers(0, vocab, (b, s)), jnp.int32)}


def _params(model, batch):
    return model.init({"params": jax.random.PRNGKey(0)},
                      batch["input_ids"], train=False)["params"]


def _leaf_paths(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in p)
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)}


def test_inject_is_identity_at_init():
    """B=0 at init → merged model bitwise equals the base model."""
    cfg = LoraConfig(rank=4)
    model = build_model(_tiny_llama(), PrecisionConfig())
    batch = _batch()
    params = _params(model, batch)
    injected = lora_lib.inject(jax.random.PRNGKey(1), params, cfg)

    added = _leaf_paths(injected) - _leaf_paths(params)
    assert added and all(p.endswith(("lora_a", "lora_b")) for p in added)
    # all four llama attention projections got adapters, per-layer
    assert sum(p.endswith("lora_a") for p in added) == 2 * 4

    base_out, _, _ = apply_model(model, params, {}, batch,
                                 train=False, dropout_rng=None)
    merged = lora_lib.merge(injected, cfg)
    merged_out, _, _ = apply_model(model, merged, {}, batch,
                                   train=False, dropout_rng=None)
    np.testing.assert_array_equal(np.asarray(base_out),
                                  np.asarray(merged_out))


def test_inject_identity_t5():
    """The default targets regex covers T5's projections too (incl. the
    out-first 3-D o_proj via out_proj_targets) — adapters attach across
    encoder self-, decoder self-, and cross-attention, identity at init."""
    cfg = LoraConfig(rank=4)
    t5_cfg = ModelConfig(name="t5", vocab_size=64, hidden_size=32,
                         num_layers=2, decoder_layers=2, num_heads=4,
                         mlp_dim=64, dropout_rate=0.0)
    model = build_model(t5_cfg, PrecisionConfig())
    src = jnp.zeros((2, 10), jnp.int32)
    tgt = jnp.zeros((2, 6), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, src, tgt,
                        train=False)["params"]
    paths = lora_lib.target_paths(params, cfg)
    # (2 enc self + 2 dec self + 2 dec cross) x q/k/v/o
    assert len(paths) == 24
    injected = lora_lib.inject(jax.random.PRNGKey(1), params, cfg)
    base = model.apply({"params": params}, src, tgt, train=False)
    merged = model.apply({"params": lora_lib.merge(injected, cfg)},
                         src, tgt, train=False)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(merged))


def test_no_targets_is_loud():
    """A targets regex that matches nothing must raise, not silently
    train zero parameters (resnet has no attention projections)."""
    model = build_model(ModelConfig(name="resnet18", num_classes=10,
                                    image_size=8), PrecisionConfig())
    params = jax.jit(lambda: model.init(  # one program, not one an op
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((2, 8, 8, 3)),
        train=False))()["params"]
    with pytest.raises(ValueError, match="matched no 2-D/3-D kernel"):
        lora_lib.inject(jax.random.PRNGKey(1), params, LoraConfig(rank=4))


def test_train_updates_adapters_only():
    """Three steps of adapter training: base leaves bitwise frozen,
    adapters move, loss falls; optimizer moments exist only at adapter
    size (the LoRA memory contract)."""
    lcfg = LoraConfig(rank=4, alpha=8.0)
    model = build_model(_tiny_llama(), PrecisionConfig())
    batch = _batch()
    loss_fn = get_loss_fn("causal_lm_xent")
    tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=1e-2, schedule="constant",
                    warmup_steps=0, weight_decay=0.0), total_steps=10)
    tx = lora_lib.mask_optimizer(tx, lcfg)

    params = lora_lib.inject(
        jax.random.PRNGKey(1), _params(model, batch), lcfg)
    state = TrainState.create(params=params, tx=tx, batch_stats={})

    # moment buffers: every array in opt_state must be adapter-shaped —
    # total moment elements == 2x adapter params (adam mu + nu), nothing
    # at base-kernel size.
    adapter_elems = sum(
        int(np.prod(l.shape))
        for p, l in jax.tree_util.tree_leaves_with_path(params)
        if str(getattr(p[-1], "key", "")) in ("lora_a", "lora_b"))
    moment_elems = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(
            state.opt_state) if getattr(l, "ndim", 0) >= 2)
    assert moment_elems == 2 * adapter_elems

    step = steps_lib.make_train_step(
        model, loss_fn, tx,
        param_transform=lambda p: lora_lib.merge(p, lcfg))
    step = jax.jit(step)
    losses = []
    for i in range(3):
        state, metrics = step(state, batch, jax.random.PRNGKey(2))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]

    before = jax.tree_util.tree_leaves_with_path(params)
    after_tree = state.params
    for path, leaf in before:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        got = after_tree
        for k in name.split("/"):
            got = got[k]
        if name.endswith("lora_b"):
            assert not np.array_equal(np.asarray(leaf), np.asarray(got)), name
        elif not name.endswith("lora_a"):
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(got), err_msg=name)


def test_mask_wraps_inside_multisteps():
    """With grad accumulation on, MultiSteps must stay the OUTERMOST
    wrapper (train_state.py's boundary detection — EMA gating, plateau
    loss routing — keys on the top-level opt_state type); the lora mask
    goes inside via make_optimizer(param_mask=...)."""
    import optax

    lcfg = LoraConfig(rank=4)
    model = build_model(_tiny_llama(), PrecisionConfig())
    params = lora_lib.inject(
        jax.random.PRNGKey(1), _params(model, _batch()), lcfg)
    tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=1e-3, schedule="constant",
                    warmup_steps=0, accum_steps=4),
        total_steps=10,
        param_mask=lambda t: lora_lib.mask_optimizer(t, lcfg))
    opt_state = tx.init(params)
    assert isinstance(opt_state, optax.MultiStepsState)


def test_strip_matches_transform_path():
    """Export: strip() removes adapters and the stripped tree's forward
    equals the in-step transform path's forward."""
    lcfg = LoraConfig(rank=4, alpha=8.0)
    model = build_model(_tiny_llama(), PrecisionConfig())
    batch = _batch()
    params = lora_lib.inject(
        jax.random.PRNGKey(1), _params(model, batch), lcfg)
    # make the adapters non-trivial so the test is not vacuous
    params = jax.tree.map(lambda x: x + 0.01 if x.ndim == 2 else x, params)

    stripped = lora_lib.strip(params, lcfg)
    assert not any(p.endswith(("lora_a", "lora_b"))
                   for p in _leaf_paths(stripped))
    assert _leaf_paths(stripped) == _leaf_paths(
        lora_lib.strip_abstract(params))

    out_a, _, _ = apply_model(model, stripped, {}, batch,
                              train=False, dropout_rng=None)
    out_b, _, _ = apply_model(model, lora_lib.merge(params, lcfg), {},
                              batch, train=False, dropout_rng=None)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               atol=1e-6)


def test_extra_trainable_unfreezes_norms():
    lcfg = LoraConfig(rank=4, extra_trainable=r"norm.*scale$")
    model = build_model(_tiny_llama(), PrecisionConfig())
    batch = _batch()
    params = lora_lib.inject(
        jax.random.PRNGKey(1), _params(model, batch), lcfg)
    labels = lora_lib.param_labels(params, lcfg)
    flat = {"/".join(str(getattr(k, "key", k)) for k in p): l
            for p, l in jax.tree_util.tree_leaves_with_path(labels)}
    assert any(k.endswith("scale") and v == "trainable"
               for k, v in flat.items())
    assert all(v == "frozen" for k, v in flat.items()
               if k.endswith("embedding"))


def test_extra_trainable_kernel_keeps_gradient():
    """A kernel matching both targets and extra_trainable must receive
    real gradients through merge (full-rank + adapter), not be silently
    stop_gradient-ed while the optimizer label says 'trainable' (which
    would leave it exposed to weight decay with zero signal)."""
    model = build_model(_tiny_llama(), PrecisionConfig())
    batch = _batch()

    def kernel_grad(lcfg):
        params = lora_lib.inject(
            jax.random.PRNGKey(1), _params(model, batch), lcfg)

        def loss(p):
            merged = lora_lib.merge(p, lcfg)
            return jnp.sum(
                merged["layer0"]["attn"]["o_proj"]["kernel"] ** 2)

        g = jax.grad(loss)(params)
        return np.asarray(g["layer0"]["attn"]["o_proj"]["kernel"])

    frozen = kernel_grad(LoraConfig(rank=4))
    assert not frozen.any()
    trained = kernel_grad(
        LoraConfig(rank=4, extra_trainable=r"o_proj/kernel$"))
    assert trained.any()


def _trainer_cfg(tmp_path, sub, lora_rank=0, base_checkpoint=""):
    cfg = TrainConfig()
    cfg.model = _tiny_llama()
    cfg.loss = "causal_lm_xent"
    cfg.data.dataset = "synthetic_lm"
    cfg.data.synthetic_size = 64
    cfg.data.batch_size = 8
    cfg.data.seq_len = 16
    cfg.data.num_workers = 1
    cfg.optim.name = "adamw"
    cfg.optim.learning_rate = 1e-3
    cfg.optim.schedule = "constant"
    cfg.optim.warmup_steps = 0
    cfg.total_steps = 2
    cfg.checkpoint.dir = str(tmp_path / sub)
    cfg.checkpoint.save_every_steps = 2
    cfg.checkpoint.async_save = False
    cfg.obs.log_every_steps = 100
    cfg.lora.rank = lora_rank
    cfg.lora.base_checkpoint = base_checkpoint
    return cfg


@pytest.mark.slow
def test_trainer_warm_start_e2e(tmp_path):
    """The full PEFT workflow: pretrain base → save → new LoRA run warm-
    starts the base subtree from that checkpoint (adapter leaves fresh),
    trains adapter-only, and its checkpoints round-trip on resume."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    base = Trainer(_trainer_cfg(tmp_path, "base"))
    base.fit()
    base_params = jax.device_get(base.state.params)
    base.close()

    ft_cfg = _trainer_cfg(tmp_path, "ft", lora_rank=4,
                          base_checkpoint=str(tmp_path / "base"))
    ft_cfg.total_steps = 2
    ft = Trainer(ft_cfg)
    # warm start happened: base leaves equal the pretrained run's params
    stripped = lora_lib.strip_abstract(jax.device_get(ft.state.params))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        stripped, base_params)
    ft.fit()
    ft_params = jax.device_get(ft.state.params)
    ft.close()

    # resume: a fresh Trainer over the same dir restores adapters exactly
    resumed = Trainer(_trainer_cfg(tmp_path, "ft", lora_rank=4))
    assert resumed.resumed
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(resumed.state.params), ft_params)
    resumed.close()
