"""Selective activation checkpointing (ModelConfig.remat_policy).

Remat is value-preserving by construction: every policy must produce
bit-identical losses and gradients; policies only move the memory/compute
trade (checked via compiled peak-memory ordering on CPU).

What the flash kernel's forward hands back is kept under every policy
(models/remat.py): the backward of a remat'd block that holds the kernel
launches it twice (forward, the fused backward), not three times.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.config import ModelConfig, PrecisionConfig
from pytorch_distributed_train_tpu.losses import get_loss_fn
from pytorch_distributed_train_tpu.models import remat
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.models.remat import POLICIES, remat_block
from pytorch_distributed_train_tpu.ops import flash_attention as fa
from pytorch_distributed_train_tpu.steps import apply_model, make_train_step


def _loss_and_grad(policy):
    cfg = ModelConfig(name="llama", vocab_size=256, hidden_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=4, mlp_dim=128,
                      max_seq_len=128, remat=True, remat_policy=policy)
    model = build_model(cfg, PrecisionConfig())
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 128)),
                      jnp.int32)
    batch = {"input_ids": ids}
    params = model.init({"params": jax.random.PRNGKey(0)}, ids,
                        train=False)["params"]

    def loss(p):
        logits, _, _ = apply_model(model, p, {}, batch, train=True,
                                   dropout_rng=None)
        return get_loss_fn("causal_lm_xent")(logits, batch)[0]

    l, g = jax.value_and_grad(loss)(params)
    return float(l), jax.tree_util.tree_leaves(g)


def test_policies_are_value_preserving():
    base_l, base_g = _loss_and_grad("full")
    for policy in ("dots", "dots_no_batch"):
        l, g = _loss_and_grad(policy)
        assert l == base_l, policy
        for a, b in zip(g, base_g):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_invalid_policy_raises():
    with pytest.raises(ValueError, match="remat_policy"):
        remat_block(object, True, "everything")
    assert remat_block(object, False, "bogus") is object  # disabled: no check
    assert set(POLICIES) == {"full", "dots", "dots_no_batch",
                             "no_fused_epilogue"}


def _flash_block_args():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
               for _ in range(3))
    return q, k, v, jnp.asarray(rng.standard_normal((64, 64)) / 8,
                                jnp.float32)


def _flash_block_loss(q, k, v, w):
    """A block's worth around the kernel (interpret mode): a product before
    it, a product and an elementwise pass after."""
    o = fa.flash_attention(q @ w, k, v, causal=True, interpret=True)
    return (jnp.tanh(o @ w) ** 2).sum()


@pytest.mark.parametrize("policy", ["full", "dots", "dots_no_batch"])
def test_a_remat_block_with_the_flash_kernel_is_value_preserving(policy):
    def loss_and_grads(fn):
        return jax.tree.leaves(jax.value_and_grad(fn, argnums=(0, 1, 2, 3))(
            *_flash_block_args()))

    base = loss_and_grads(_flash_block_loss)
    kept = loss_and_grads(jax.checkpoint(_flash_block_loss,
                                         policy=POLICIES[policy]))
    for a, b in zip(kept, base):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _launches(policy):
    # a function of its own, so that the trace asks the policy and is not
    # answered from JAX's cache of an earlier test's
    fn = jax.checkpoint(lambda *args: _flash_block_loss(*args), policy=policy)
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2, 3)))(
        *_flash_block_args()))
    return text.count("pallas_call")


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_the_backward_of_a_remat_block_launches_no_second_forward(policy):
    """Forward and the one fused backward: TWO launches under every policy,
    and three under the policy each was before it named the kernel's tag
    (the test that fails if a later edit drops the tag or the name from a
    policy)."""
    remat.kept.clear()
    assert _launches(POLICIES[policy]) == 2
    assert remat.kept == {fa.FLASH_RESIDUALS_NAME}
    without = {
        "full": None,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # keeps everything but its own names: nothing to take out
        "no_fused_epilogue": None,
    }[policy]
    assert _launches(without) == 3


@pytest.mark.parametrize("remat_on,keeps", [(True, "flash_out+lse"),
                                            (False, "none")])
def test_the_step_says_what_its_remat_keeps(remat_on, keeps):
    """`resolved`, which the trainer's train.compile span carries: the tag's
    name where a remat'd block held the kernel (forced here, in interpret
    mode), `none` with remat off and on the XLA attention paths."""
    import optax

    from pytorch_distributed_train_tpu.train_state import TrainState

    for impl, said in (("pallas", keeps), ("xla", "none")):
        cfg = ModelConfig(name="llama", vocab_size=256, hidden_size=128,
                          num_layers=1, num_heads=2, num_kv_heads=2,
                          mlp_dim=128, max_seq_len=128, remat=remat_on,
                          attention_impl=impl)
        model = build_model(cfg, PrecisionConfig(compute_dtype="float32"))
        ids = jnp.zeros((1, 128), jnp.int32)
        tx = optax.sgd(0.1)

        def init(rng):
            params = model.init({"params": rng}, ids, train=False)["params"]
            return TrainState.create(params=params, tx=tx, batch_stats={},
                                     dynamic_scale=None, ema=False, swa=False)

        step = make_train_step(model, get_loss_fn("causal_lm_xent"), tx)
        assert step.resolved["remat_keeps"] == "none"
        jax.eval_shape(step, jax.eval_shape(init, jax.random.PRNGKey(0)),
                       {"input_ids": ids}, jax.random.PRNGKey(1))
        assert step.resolved["remat_keeps"] == said, impl
