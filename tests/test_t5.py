"""T5 encoder-decoder (models/t5.py): bucketing math, decoder causality,
encoder masking, FSDP/TP sharding rules, and the Trainer e2e on the
seq2seq objective. Golden numerics vs HF live in test_hf_parity.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_train_tpu.config import (
    ModelConfig,
    PrecisionConfig,
    TrainConfig,
)
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.models.t5 import relative_position_bucket

V = 64


def _cfg(**kw):
    base = dict(name="t5", vocab_size=V, hidden_size=32, num_layers=2,
                decoder_layers=2, num_heads=4, mlp_dim=64, dropout_rate=0.0)
    base.update(kw)
    return ModelConfig(**base)


def _model_and_params(cfg=None, se=10, sd=6):
    cfg = cfg or _cfg()
    model = build_model(cfg, PrecisionConfig())
    src = jnp.zeros((2, se), jnp.int32)
    tgt = jnp.zeros((2, sd), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, src, tgt,
                        train=False)["params"]
    return model, params


def test_relative_position_bucket_matches_hf():
    """Pin the bucketing against HF's torch implementation directly —
    the one piece of T5 most likely to drift (log-spaced far buckets)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf_fn = transformers.models.t5.modeling_t5.T5Attention._relative_position_bucket
    rel = (np.arange(40)[None, :] - np.arange(40)[:, None]).astype(np.int32)
    for bidirectional in (True, False):
        ours = np.asarray(relative_position_bucket(
            jnp.asarray(rel), bidirectional, 32, 128))
        theirs = hf_fn(torch.from_numpy(rel).long(),
                       bidirectional=bidirectional,
                       num_buckets=32, max_distance=128).numpy()
        np.testing.assert_array_equal(ours, theirs)


def test_decoder_is_causal():
    """Changing a decoder token must not change logits at earlier
    positions (the cross-attended encoder is held fixed)."""
    model, params = _model_and_params()
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(0, V, (1, 10)), jnp.int32)
    tgt = np.asarray(rng.integers(0, V, (1, 6)), np.int32)
    base = model.apply({"params": params}, src, jnp.asarray(tgt),
                       train=False)
    tgt2 = tgt.copy()
    tgt2[0, 4] = (tgt2[0, 4] + 1) % V
    pert = model.apply({"params": params}, src, jnp.asarray(tgt2),
                       train=False)
    np.testing.assert_array_equal(np.asarray(base[:, :4]),
                                  np.asarray(pert[:, :4]))
    assert not np.allclose(np.asarray(base[:, 4:]), np.asarray(pert[:, 4:]))


def test_encoder_mask_blocks_padding():
    """A masked-out encoder token must not influence decoder logits; an
    unmasked change must."""
    model, params = _model_and_params()
    rng = np.random.default_rng(1)
    src = np.asarray(rng.integers(0, V, (1, 10)), np.int32)
    tgt = jnp.asarray(rng.integers(0, V, (1, 6)), jnp.int32)
    mask = np.ones((1, 10), np.int32)
    mask[0, -2:] = 0
    base = model.apply({"params": params}, jnp.asarray(src), tgt,
                       train=False, attention_mask=jnp.asarray(mask))
    src2 = src.copy()
    src2[0, -1] = (src2[0, -1] + 1) % V  # masked position
    out2 = model.apply({"params": params}, jnp.asarray(src2), tgt,
                       train=False, attention_mask=jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out2))
    src3 = src.copy()
    src3[0, 0] = (src3[0, 0] + 1) % V  # attended position
    out3 = model.apply({"params": params}, jnp.asarray(src3), tgt,
                       train=False, attention_mask=jnp.asarray(mask))
    assert not np.allclose(np.asarray(base), np.asarray(out3))


def test_batch_attention_mask_reaches_encoder():
    """The Trainer path (steps.apply_model) must forward a seq2seq
    batch's attention_mask to the model — a masked source token change
    must not alter logits through that path."""
    from pytorch_distributed_train_tpu.steps import apply_model

    model, params = _model_and_params()
    rng = np.random.default_rng(2)
    src = np.asarray(rng.integers(0, V, (1, 10)), np.int32)
    batch = {
        "input_ids": jnp.asarray(src),
        "decoder_input_ids": jnp.asarray(
            rng.integers(0, V, (1, 6)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, V, (1, 6)), jnp.int32),
        "attention_mask": jnp.asarray(
            np.concatenate([np.ones((1, 8), np.int32),
                            np.zeros((1, 2), np.int32)], 1)),
    }
    base, _, _ = apply_model(model, params, {}, batch, train=False,
                             dropout_rng=None)
    src2 = src.copy()
    src2[0, -1] = (src2[0, -1] + 1) % V  # masked position
    batch2 = {**batch, "input_ids": jnp.asarray(src2)}
    out2, _, _ = apply_model(model, params, {}, batch2, train=False,
                             dropout_rng=None)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out2))


def test_dropout_active_in_train_mode():
    """dropout_rate>0 + train=True must be stochastic (covers the
    attention-probability dropout alongside the sublayer dropouts)."""
    cfg = _cfg(dropout_rate=0.3)
    model = build_model(cfg, PrecisionConfig())
    src = jnp.zeros((2, 10), jnp.int32)
    tgt = jnp.zeros((2, 6), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, src, tgt,
                        train=False)["params"]
    o1 = model.apply({"params": params}, src, tgt, train=True,
                     rngs={"dropout": jax.random.PRNGKey(1)})
    o2 = model.apply({"params": params}, src, tgt, train=True,
                     rngs={"dropout": jax.random.PRNGKey(2)})
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


def test_remat_preserves_fwd_and_grad():
    """model.remat=true on t5 must be numerically inert (same forward,
    same grads — it only trades backward FLOPs for activation memory)."""
    src = jnp.zeros((2, 10), jnp.int32)
    tgt = jnp.zeros((2, 6), jnp.int32)
    outs = {}
    for remat in (False, True):
        model = build_model(_cfg(remat=remat), PrecisionConfig())
        params = model.init({"params": jax.random.PRNGKey(0)}, src, tgt,
                            train=False)["params"]

        def loss(p):
            return jnp.sum(model.apply({"params": p}, src, tgt,
                                       train=True) ** 2)

        # one program each, not one per op (op by op, every new shape
        # compiles alone: most of this test's minute)
        value, grads = jax.jit(jax.value_and_grad(loss))(params)
        outs[remat] = (float(value), grads)
    np.testing.assert_allclose(outs[False][0], outs[True][0], rtol=1e-6)
    # remat reorders the recompute, so bit-exactness isn't guaranteed;
    # near-cancelling gradient elements carry fp32 accumulation noise
    # proportional to the LOSS scale (O(1e3) here), not their own tiny
    # values — compare at that floor
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=5e-4),
        outs[False][1], outs[True][1])


def test_sharding_rules_cover_t5(devices8):
    """Every t5 param gets a valid spec on a fsdp×tensor mesh."""
    from jax.sharding import Mesh
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )

    _, params = _model_and_params()
    mesh = Mesh(np.array(devices8).reshape(4, 2), ("fsdp", "tensor"))
    shardings = rules_for_model("t5").tree_shardings(mesh, params)
    flat = {"/".join(str(getattr(k, "key", k)) for k in p): s
            for p, s in jax.tree_util.tree_leaves_with_path(shardings)}
    # the big matmuls must actually shard (not fall back to replicated)
    assert "fsdp" in str(flat["shared/embedding"].spec)
    assert "tensor" in str(flat["enc_block0/self_attn/q_proj/kernel"].spec)
    assert "tensor" in str(flat["dec_block1/mlp/wo/kernel"].spec)


@pytest.mark.parametrize("tied", [False, True])
def test_seq2seq_decode_matches_teacher_forced(tied):
    """Cached single-token decoding must reproduce greedy teacher-forced
    decoding with the full training model, token for token — pins the
    decode cache, the per-step relative-bias lookup, and the cross-
    attention path (both head variants)."""
    from pytorch_distributed_train_tpu.generate import generate_seq2seq

    cfg = _cfg(tie_word_embeddings=tied)
    model, params = _model_and_params(cfg)
    rng = np.random.default_rng(3)
    src = jnp.asarray(rng.integers(0, V, (2, 10)), jnp.int32)
    n = 8

    # (one jitted program a prefix length: op by op, every op of the model
    # compiled again at each of the eight lengths)
    forward = jax.jit(lambda p, s, t: model.apply(
        {"params": p}, s, t, train=False))
    prefix = np.zeros((2, 1), np.int32)  # decoder_start_id = 0
    ref = []
    for _ in range(n):
        logits = forward(params, src, jnp.asarray(prefix))
        tok = np.asarray(jnp.argmax(logits[:, -1], -1), np.int32)
        ref.append(tok)
        prefix = np.concatenate([prefix, tok[:, None]], axis=1)
    ref = np.stack(ref, axis=1)

    out = generate_seq2seq(cfg, PrecisionConfig(), params, src, n,
                           temperature=0.0, eos_id=None)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_seq2seq_decode_respects_encoder_mask():
    """Padded source positions must not affect generation."""
    from pytorch_distributed_train_tpu.generate import generate_seq2seq

    cfg = _cfg()
    _, params = _model_and_params(cfg)
    rng = np.random.default_rng(4)
    src = np.asarray(rng.integers(0, V, (1, 10)), np.int32)
    mask = np.ones((1, 10), np.int32)
    mask[0, -2:] = 0
    out1 = generate_seq2seq(cfg, PrecisionConfig(), params,
                            jnp.asarray(src), 6, attention_mask=mask,
                            eos_id=None)
    src2 = src.copy()
    src2[0, -1] = (src2[0, -1] + 1) % V
    out2 = generate_seq2seq(cfg, PrecisionConfig(), params,
                            jnp.asarray(src2), 6, attention_mask=mask,
                            eos_id=None)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


@pytest.mark.slow
def test_t5_trainer_e2e(tmp_path):
    """Two steps of seq2seq training through the full Trainer (8-device
    DP mesh, synthetic seq2seq data, loss finite and improving-or-sane),
    plus checkpoint save."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = TrainConfig()
    cfg.model = _cfg(max_seq_len=32)
    cfg.loss = "seq2seq_xent"
    cfg.data.dataset = "synthetic_seq2seq"
    cfg.data.seq_len = 16
    cfg.data.tgt_seq_len = 8
    cfg.data.synthetic_size = 64
    cfg.data.batch_size = 8
    cfg.data.num_workers = 1
    cfg.optim.name = "adamw"
    cfg.optim.learning_rate = 1e-3
    cfg.optim.schedule = "constant"
    cfg.optim.warmup_steps = 0
    cfg.total_steps = 2
    cfg.checkpoint.dir = str(tmp_path / "t5")
    cfg.checkpoint.save_every_steps = 2
    cfg.checkpoint.async_save = False
    cfg.obs.log_every_steps = 100
    t = Trainer(cfg)
    state = t.fit()
    assert int(state.step) == 2
    t.close()


def test_t5_fp8_kv_cache_decode():
    """kv_cache_dtype=float8_e4m3fn on the t5 decoder self-attention cache
    (cross-attention recomputes from the encoder, no cache): buffers store
    fp8 and greedy generation tracks the full-precision cache."""
    import dataclasses

    import numpy as np

    from pytorch_distributed_train_tpu.generate import generate_seq2seq

    cfg = _cfg()
    _, params = _model_and_params(cfg)
    prec = PrecisionConfig(compute_dtype="float32")
    src = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 6)),
        jnp.int32)
    ref = np.asarray(generate_seq2seq(cfg, prec, params, src, 6,
                                      temperature=0.0, eos_id=None))
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="float8_e4m3fn")
    out = np.asarray(generate_seq2seq(cfg8, prec, params, src, 6,
                                      temperature=0.0, eos_id=None))
    assert (ref == out).mean() >= 0.75, (ref, out)
