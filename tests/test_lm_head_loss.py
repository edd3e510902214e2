"""The LM head's kernels (ops/lm_head_loss.py: the loss's passes over the
logits ride the head's products) against the dense float32 ``dot`` + optax
reference, in interpret mode on the CPU; the training step that reaches
them with no knob (steps._head_loss_plan, ops/lm_head.py); and the paths
that need logits and still get them. Whether Mosaic takes the kernels at
GPT-2 small's shape, and what the compiled steps hold, is
tests/test_tpu_compile.py's; how fast they are is a chip run's (PERF.md).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_train_tpu import losses as losses_lib
from pytorch_distributed_train_tpu import steps as steps_lib
from pytorch_distributed_train_tpu.config import (
    ModelConfig,
    PrecisionConfig,
    get_preset,
)
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.ops import attention as attention_lib
from pytorch_distributed_train_tpu.ops import lm_head as lm_head_lib
from pytorch_distributed_train_tpu.ops import lm_head_loss as kernels
from pytorch_distributed_train_tpu.optim import make_optimizer
from pytorch_distributed_train_tpu.train_state import TrainState

# (id, rows, vocab, width, dtype, (V, C) table?, zeros in the mask?, tiles)
KERNEL_CASES = [
    ("whole_tiles", 256, 512, 128, "float32", True, False, (128, 256, 128)),
    ("ragged_last_tile", 256, 648, 128, "float32", True, False,
     (128, 256, 64)),
    ("loss_mask_with_zeros", 256, 648, 64, "float32", True, True,
     (256, 256, 256)),
    ("plain_cv_kernel", 256, 648, 64, "float32", False, True,
     (128, 512, 128)),
    ("bfloat16_operands", 512, 776, 128, "bfloat16", True, True,
     (256, 256, 128)),
    ("the_rules_own_tiles", 1024, 1160, 128, "bfloat16", True, True, None),
]


def _operands(rows, vocab, width, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], (rows, width), jnp.float32).astype(dtype)
    w = (0.3 * jax.random.normal(k[1], (vocab, width), jnp.float32)
         ).astype(dtype)
    labels = jax.random.randint(k[2], (rows,), 0, vocab)
    keep = (jax.random.uniform(k[3], (rows,)) > 0.3).astype(jnp.float32)
    return x, w, labels, keep


def _mean(per_tok, weights):
    return (per_tok * weights).sum() / jnp.maximum(weights.sum(), 1.0)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize(
    "rows,vocab,width,dtype,table_vc,masked,tiles",
    [c[1:] for c in KERNEL_CASES], ids=[c[0] for c in KERNEL_CASES])
def test_kernels_match_the_dense_reference(rows, vocab, width, dtype,
                                           table_vc, masked, tiles):
    """Loss, dX and dW. Float32 operands: equal to the dense float32
    reference to float32 rounding. bfloat16 operands: the rounding points
    are the logits path's on a TPU (``dl`` rounded to bfloat16 once,
    float32 accumulation), not merely close to float32."""
    x, w, labels, keep = _operands(rows, vocab, width, dtype)
    weights = keep if masked else jnp.ones_like(keep)
    kw = dict(zip(("tile_n", "tile_v", "sub_v"), tiles)) if tiles else {}
    if tiles is None:  # the rule picks what divides, the vocabulary ragged
        assert kernels.tile_sizes(rows, vocab) == (1024, 1280, 256)
    head_w = w if table_vc else w.T

    def fused(x, hw):
        return _mean(kernels.token_xent(
            x, hw, labels, transposed_w=table_vc, interpret=True, **kw),
            weights)

    def logits_path(x, hw):
        logits = jax.lax.dot_general(
            x, hw, (((1,), (1 if table_vc else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return _mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, labels), weights)

    loss, (dx, dw) = jax.value_and_grad(fused, (0, 1))(x, head_w)
    assert dx.dtype == x.dtype and dw.dtype == head_w.dtype
    assert dw.shape == head_w.shape
    want_loss, (want_dx, want_dw) = jax.value_and_grad(
        logits_path, (0, 1))(x, head_w)
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
    if dtype == "float32":
        assert _rel(dx, want_dx) < 2e-6 and _rel(dw, want_dw) < 2e-6
        return
    # On a TPU the logits path rounds dl to bfloat16 on its way into the
    # MXU (the CPU's dot keeps it float32): the kernels round it there
    # too, so their gradients sit on that reference, built here by hand,
    # several times closer than on the one with dl left in float32.
    logits = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scale = weights / jnp.maximum(weights.sum(), 1.0)
    dl = ((jax.nn.softmax(logits) - jax.nn.one_hot(labels, vocab))
          * scale[:, None]).astype(dtype)
    rounded = (jnp.dot(dl, w, preferred_element_type=jnp.float32),
               jnp.dot(dl.T, x, preferred_element_type=jnp.float32))
    for got, want, unrounded in zip((dx, dw), rounded, (want_dx, want_dw)):
        want = want.astype(dtype)
        assert _rel(got, want) < 0.25 * _rel(got, unrounded), (
            _rel(got, want), _rel(got, unrounded))
        assert _rel(got, unrounded) < 4e-3


def test_a_masked_row_gets_a_zero_gradient_and_any_label():
    """The shift rides as a weight of 0 on each sequence's last position,
    whose label is padding: its row of dl is exactly 0."""
    x, w, labels, _ = _operands(128, 256, 64, "float32")
    weights = jnp.ones((128,)).at[-1].set(0.0)

    def loss(x, labels):
        return _mean(kernels.token_xent(x, w, labels, interpret=True),
                     weights)

    g0 = jax.grad(loss)(x, labels)
    g1 = jax.grad(loss)(x, labels.at[-1].set(0))
    assert np.all(np.asarray(g0[-1]) == 0.0)
    np.testing.assert_array_equal(g0, g1)


# --------------------------------------------------- the step that reaches it

TINY_LM = ["model.hidden_size=128", "model.num_layers=1", "model.num_heads=2",
           "model.mlp_dim=128", "model.vocab_size=328",
           "model.max_seq_len=64", "model.dropout_rate=0.0",
           "data.seq_len=64", "data.batch_size=4",
           # the CPU's logits path keeps dl float32 where a TPU's rounds it
           # (above): float32 operands make the two paths comparable here
           "precision.compute_dtype=float32"]


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The dispatch asks the runtime backend, the CPU here: steer it in the
    test (the kernels then run in the interpreter), not through an option."""
    monkeypatch.setattr(attention_lib, "_on_tpu", lambda: True)
    monkeypatch.setattr(lm_head_lib, "_interpret", lambda: True)
    lm_head_lib._logged.clear()


def _step_parts(preset, overrides, **model_kw):
    cfg = get_preset(preset)
    cfg.apply_overrides(overrides)
    model = build_model(cfg.model, cfg.precision, **model_kw)
    tx, _ = make_optimizer(cfg.optim, cfg.total_steps, 0)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.model.vocab_size, (cfg.data.batch_size, cfg.data.seq_len)),
        jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, ids,
                        train=False)["params"]
    state = TrainState.create(params=params, tx=tx, batch_stats={},
                              dynamic_scale=None, ema=False, swa=False)
    return cfg, model, tx, state, {"input_ids": ids}


def _traced(step, *args):
    return str(jax.make_jaxpr(step)(*args))


def test_gpt2s_training_step_takes_the_kernels_with_no_knob(as_on_a_tpu,
                                                            capfd):
    """The preset's own settings (loss causal_lm_xent, no fused_lm_loss)
    land on the new path; the step's loss and update are the logits
    path's; one line says what was built."""
    cfg, model, tx, state, batch = _step_parts("gpt2_small", TINY_LM)
    assert cfg.loss == "causal_lm_xent" and not cfg.model.fused_lm_loss
    batch["loss_mask"] = jnp.ones((4, 64), jnp.float32).at[1, 5:9].set(0.0)
    loss_fn = losses_lib.get_loss_fn(cfg.loss)
    rng = jax.random.PRNGKey(1)
    step = steps_lib.make_train_step(model, loss_fn, tx)
    assert "pallas_call" in _traced(step, state, batch, rng)
    new_state, metrics = jax.jit(step)(state, batch, rng)
    err = capfd.readouterr().err
    assert ("[lm_head] impl=pallas rows=256 vocab=328 width=128 "
            "tiles=256x384 ragged_cols=328 logits=float32") in err
    assert step.resolved == {"head_loss": "pallas", "remat_keeps": "none"}

    # the same step with the model's capability hidden: the logits path
    class NoOperands:
        def __init__(self, m):
            self.apply, self.fused_loss = m.apply, False

    want_state, want = jax.jit(steps_lib.make_train_step(
        NoOperands(model), loss_fn, tx))(state, batch, rng)
    assert "does not offer its head's operands" in capfd.readouterr().err
    np.testing.assert_allclose(metrics["loss"], want["loss"], rtol=1e-6)
    np.testing.assert_allclose(metrics["grad_norm"], want["grad_norm"],
                               rtol=1e-4)
    for a, b in zip(jax.tree.leaves(new_state.params),
                    jax.tree.leaves(want_state.params)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-6)


# (id, what is built, the reason the line gives or None for no line)
LOGITS_PATHS = [
    ("evaluation", "eval", None),
    ("generate", "generate", None),
    ("distill", "distill", "the loss's teacher term reads the logits"),
    ("hybrid_model", "hybrid",
     "HybridLM does not offer its head's operands"),
    ("rows_do_not_tile", "odd_rows", "rows=192 is not a multiple of 128"),
    ("not_a_tpu", "cpu", "the backend is not a TPU"),
]


@pytest.mark.parametrize("what,reason", [c[1:] for c in LOGITS_PATHS],
                         ids=[c[0] for c in LOGITS_PATHS])
def test_what_needs_the_logits_still_gets_them(as_on_a_tpu, monkeypatch,
                                               capfd, what, reason):
    """Evaluation, generate, distillation, a model without the capability
    and shapes or a backend the kernels cannot take trace no head kernel;
    a TRAINING step that keeps the logits path says so, with the reason."""
    rng = jax.random.PRNGKey(1)
    overrides = list(TINY_LM)
    if what == "odd_rows":
        overrides += ["data.seq_len=48"]
    if what == "cpu":
        monkeypatch.setattr(attention_lib, "_on_tpu", lambda: False)
    if what == "hybrid":
        import json
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               "ling3_flash_lm_ep64.json")) as f:
            bench = json.load(f)
        cfg, model, tx, state, batch = _step_parts(
            bench["preset"], list(bench["rehearsal_overrides"]) + [
                "data.seq_len=128", "data.batch_size=2"])
        assert not hasattr(model, "head_operands")
    else:
        cfg, model, tx, state, batch = _step_parts("gpt2_small", overrides)
    loss_fn = losses_lib.get_loss_fn(cfg.loss)
    if what == "eval":
        text = _traced(steps_lib.make_eval_step(model, loss_fn), state, batch)
    elif what == "generate":
        from pytorch_distributed_train_tpu import generate as generate_lib

        decoder = generate_lib.build_decode_model(cfg.model, cfg.precision)
        assert hasattr(decoder, "head_operands") and not decoder.head_operands
        text = _traced(
            lambda p, ids: generate_lib.generate(
                decoder, p, ids, 4, temperature=0.0), state.params,
            batch["input_ids"][:, :8])
    else:
        teacher_fn = None
        if what == "distill":
            from pytorch_distributed_train_tpu import distill as distill_lib

            teacher_fn = distill_lib.make_teacher_fn(
                model, {"params": state.params})
            loss_fn = losses_lib.make_distill_loss(loss_fn, cfg.loss, 0.5,
                                                   2.0)
        step = steps_lib.make_train_step(model, loss_fn, tx,
                                         teacher_fn=teacher_fn)
        text = _traced(step, state, batch, rng)
        assert step.resolved == {"head_loss": f"xla: {reason}",
                                 "remat_keeps": "none"}
    assert "pallas_call" not in text
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("[lm_head]")]
    if reason is None:
        assert lines == []
    else:
        assert len(lines) == 1 and re.match(
            r"\[lm_head\] impl=xla rows=\d+ vocab=\d+ width=(\d+|-) "
            rf"reason=.*{re.escape(reason)}", lines[0]), lines


def test_other_losses_steps_trace_what_they_traced(as_on_a_tpu, capfd):
    """A loss that is not causal_lm_xent: the plan has nothing to say, the
    model is the caller's own object, no line is printed."""
    model = build_model(
        ModelConfig(name="gpt2", vocab_size=328, hidden_size=128,
                    num_layers=1, num_heads=2, mlp_dim=128, max_seq_len=64),
        PrecisionConfig(compute_dtype="float32"))
    for loss_fn in (losses_lib.mlm_xent, losses_lib.fused_causal_lm_xent,
                    losses_lib.make_grpo_loss()):
        planned, why = steps_lib._head_loss_plan(model, loss_fn, None)
        assert planned is model and why is None
    planned, why = steps_lib._head_loss_plan(
        model, losses_lib.causal_lm_xent, None)
    assert planned.head_operands and not model.head_operands and why is None
    assert capfd.readouterr().err == ""
    step = steps_lib.make_train_step(model, losses_lib.mlm_xent, None)
    assert step.resolved == {"head_loss": "none", "remat_keeps": "none"}
