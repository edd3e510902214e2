"""Model zoo shape/init tests (tiny configs — CPU-fast)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_train_tpu.config import ModelConfig, PrecisionConfig
from pytorch_distributed_train_tpu.models.registry import build_model, list_models

P32 = PrecisionConfig()


def _init_and_apply(model, *inputs, train=False):
    """Init and one apply as ONE jitted program: op by op, every primitive
    of every new shape compiles alone, which was most of this file's time."""
    def run(rng):
        variables = model.init({"params": rng}, *inputs, train=False)
        mutable = ["batch_stats"] if "batch_stats" in variables else False
        out = model.apply(variables, *inputs, train=train,
                          rngs={"dropout": jax.random.PRNGKey(1)},
                          mutable=mutable)
        return (out[0] if mutable else out), variables

    return jax.jit(run)(jax.random.PRNGKey(0))


def test_registry_lists_all_families():
    assert list_models() == ["bert_base", "gpt2", "hybrid_lm", "llama",
                             "llama_pp", "resnet18", "resnet50", "t5",
                             "vit_b16"]


def test_resnet18_cifar_shapes():
    cfg = ModelConfig(name="resnet18", num_classes=10, image_size=32)
    model = build_model(cfg, P32)
    x = jnp.zeros((4, 32, 32, 3))
    logits, variables = _init_and_apply(model, x)
    assert logits.shape == (4, 10)
    assert "batch_stats" in variables  # BN running stats present


def test_resnet50_imagenet_stem():
    cfg = ModelConfig(name="resnet50", num_classes=1000, image_size=64)
    model = build_model(cfg, P32)
    x = jnp.zeros((2, 64, 64, 3))
    logits, _ = _init_and_apply(model, x)
    assert logits.shape == (2, 1000)


def test_vit_tiny_shapes():
    cfg = ModelConfig(name="vit_b16", num_classes=10, image_size=32, patch_size=8,
                      hidden_size=64, num_layers=2, num_heads=4, mlp_dim=128,
                      dropout_rate=0.1)
    model = build_model(cfg, P32)
    x = jnp.zeros((2, 32, 32, 3))
    logits, variables = _init_and_apply(model, x, train=True)
    assert logits.shape == (2, 10)
    # 4x4 patches + CLS
    assert variables["params"]["pos_embed"].shape == (1, 17, 64)


def test_bert_tiny_shapes():
    cfg = ModelConfig(name="bert_base", vocab_size=1000, hidden_size=64,
                      num_layers=2, num_heads=4, mlp_dim=128, max_seq_len=64)
    model = build_model(cfg, P32)
    ids = jnp.zeros((2, 16), jnp.int32)
    mask = jnp.ones((2, 16), jnp.int32)
    logits, _ = _init_and_apply(model, ids, mask)
    assert logits.shape == (2, 16, 1000)


def test_llama_tiny_shapes_and_causality():
    cfg = ModelConfig(name="llama", vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, mlp_dim=128, max_seq_len=32,
                      remat=False)
    model = build_model(cfg, P32)
    ids = jnp.asarray(np.arange(32)[None] % 256, jnp.int32)
    logits, variables = _init_and_apply(model, ids)
    assert logits.shape == (1, 32, 256)

    # causality: changing a future token must not affect past logits
    ids2 = ids.at[0, 20].set(99)
    logits2 = model.apply(variables, ids2, train=False)
    np.testing.assert_allclose(
        np.asarray(logits[0, :20]), np.asarray(logits2[0, :20]), atol=1e-5
    )
    assert not np.allclose(np.asarray(logits[0, 20:]), np.asarray(logits2[0, 20:]))


def test_bf16_policy_keeps_params_fp32():
    cfg = ModelConfig(name="resnet18", num_classes=10, image_size=32)
    model = build_model(cfg, PrecisionConfig(compute_dtype="bfloat16"))
    x = jnp.zeros((2, 32, 32, 3))
    logits, variables = _init_and_apply(model, x)
    # params stay fp32 (master weights), logits come back fp32
    kernels = jax.tree_util.tree_leaves(variables["params"])
    assert all(k.dtype == jnp.float32 for k in kernels)
    assert logits.dtype == jnp.float32


def test_gqa_repeat_matches_mha_when_equal():
    from pytorch_distributed_train_tpu.ops.attention import dot_product_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    full = dot_product_attention(q, k, v)
    # kv with 2 heads repeated manually == GQA path with 2 kv heads
    k2, v2 = k[:, :, :2], v[:, :, :2]
    gqa = dot_product_attention(q, k2, v2)
    manual = dot_product_attention(
        q, jnp.repeat(k2, 2, axis=2), jnp.repeat(v2, 2, axis=2)
    )
    np.testing.assert_allclose(np.asarray(gqa), np.asarray(manual), atol=1e-6)
    assert full.shape == gqa.shape


def test_gpt2_tiny_shapes_and_causality():
    cfg = ModelConfig(name="gpt2", vocab_size=64, hidden_size=32,
                      num_layers=2, num_heads=2, mlp_dim=48, max_seq_len=16,
                      dropout_rate=0.0)
    model = build_model(cfg, PrecisionConfig())
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 10)),
                      jnp.int32)
    variables = model.init({"params": jax.random.PRNGKey(0)}, ids,
                           train=False)
    logits = model.apply(variables, ids, train=False)
    assert logits.shape == (2, 10, 64) and logits.dtype == jnp.float32

    # causality: changing a future token must not affect earlier logits
    ids2 = ids.at[:, 7].set((ids[:, 7] + 1) % 64)
    logits2 = model.apply(variables, ids2, train=False)
    np.testing.assert_allclose(np.asarray(logits[:, :7]),
                               np.asarray(logits2[:, :7]),
                               atol=1e-6, rtol=1e-6)
    assert not np.allclose(np.asarray(logits[:, 7:]),
                           np.asarray(logits2[:, 7:]))


def test_space_to_depth_stem_is_exact():
    """The s2d stem must be a mathematically exact rewrite: identical
    params (same (7,7,3,F) kernel path), identical logits for any input."""
    import jax
    import numpy as np
    from pytorch_distributed_train_tpu.config import ModelConfig, PrecisionConfig
    from pytorch_distributed_train_tpu.models.registry import build_model

    cfg = ModelConfig(name="resnet50", num_classes=10, image_size=32)
    base = build_model(cfg, PrecisionConfig())
    import dataclasses
    s2d = build_model(dataclasses.replace(cfg, stem="space_to_depth"),
                      PrecisionConfig())
    x = jax.numpy.asarray(
        np.random.default_rng(0).standard_normal((2, 32, 32, 3)),
        jax.numpy.float32)
    v = base.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    # same param tree structure → the s2d model accepts the conv params
    v2 = s2d.init({"params": jax.random.PRNGKey(1)}, x, train=False)
    assert (jax.tree_util.tree_structure(v["params"])
            == jax.tree_util.tree_structure(v2["params"]))
    assert v["params"]["conv_stem"]["kernel"].shape == (7, 7, 3, 64)
    out_base = base.apply(v, x, train=False)
    out_s2d = s2d.apply(v, x, train=False)
    np.testing.assert_allclose(np.asarray(out_base), np.asarray(out_s2d),
                               rtol=2e-4, atol=2e-4)

    # odd image dims are rejected (the 2x2 regroup needs even H/W)
    import pytest
    xo = jax.numpy.zeros((1, 31, 31, 3))
    with pytest.raises(ValueError, match="even image dims"):
        s2d.init({"params": jax.random.PRNGKey(0)}, xo, train=False)


def test_unknown_stem_rejected():
    import dataclasses
    import jax
    import pytest
    from pytorch_distributed_train_tpu.config import ModelConfig, PrecisionConfig
    from pytorch_distributed_train_tpu.models.registry import build_model

    bad = build_model(
        dataclasses.replace(
            ModelConfig(name="resnet50", num_classes=10, image_size=32),
            stem="s2d"),
        PrecisionConfig())
    with pytest.raises(ValueError, match="unknown stem"):
        bad.init({"params": jax.random.PRNGKey(0)},
                 jax.numpy.zeros((1, 32, 32, 3)), train=False)


def test_rope_linear_scaling_interpolates_positions():
    """rope(t, scaling=k) must equal rope(t/k) exactly (linear position
    interpolation), and the scaled model runs fwd + decode at 2x the
    nominal context grid."""
    import dataclasses
    import jax
    import numpy as np
    from pytorch_distributed_train_tpu.models.llama import rope_frequencies
    from pytorch_distributed_train_tpu.config import ModelConfig, PrecisionConfig
    from pytorch_distributed_train_tpu.models.registry import build_model

    cos1, sin1 = rope_frequencies(8, 16, 10000.0, scaling=1.0)
    cos2, sin2 = rope_frequencies(8, 32, 10000.0, scaling=2.0)
    # every second scaled position lands exactly on an unscaled one
    np.testing.assert_allclose(np.asarray(cos2[::2]), np.asarray(cos1),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sin2[::2]), np.asarray(sin1),
                               rtol=1e-6)

    cfg = ModelConfig(name="llama", vocab_size=64, hidden_size=32,
                      num_layers=1, num_heads=2, num_kv_heads=2, mlp_dim=64,
                      max_seq_len=32, rope_scaling=2.0)
    model = build_model(cfg, PrecisionConfig())
    ids = jax.numpy.zeros((1, 32), jax.numpy.int32)
    v = model.init({"params": jax.random.PRNGKey(0)}, ids, train=False)
    logits = model.apply(v, ids, train=False)
    assert logits.shape == (1, 32, 64)
    assert np.all(np.isfinite(np.asarray(logits)))
    # unscaled model at the same params gives DIFFERENT logits beyond the
    # trivial position (scaling actually changes the encoding)
    base = build_model(dataclasses.replace(cfg, rope_scaling=1.0),
                       PrecisionConfig())
    logits_b = base.apply(v, ids, train=False)
    assert not np.allclose(np.asarray(logits), np.asarray(logits_b))
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(logits_b[:, 0]), rtol=2e-4)


def test_rope_ntk_scaling_preserves_high_frequencies():
    """NTK-aware scaling: the highest-frequency rotary pair (i=0,
    inv_freq=1 regardless of base) is EXACTLY the unscaled rope, while
    the lowest frequency stretches ~scaling x (the recipe's point:
    local order intact, long-range capacity extended). Linear scaling by
    contrast compresses every frequency uniformly."""
    import numpy as np

    from pytorch_distributed_train_tpu.models.llama import rope_frequencies

    D, S, theta, k = 64, 128, 10000.0, 4.0
    cos0, sin0 = rope_frequencies(D, S, theta)
    cos_ntk, sin_ntk = rope_frequencies(D, S, theta, k, "ntk")
    cos_lin, _ = rope_frequencies(D, S, theta, k, "linear")

    # i=0: inv_freq = theta'^0 = 1 for ANY base — identical to unscaled
    np.testing.assert_allclose(np.asarray(cos_ntk[:, 0]),
                               np.asarray(cos0[:, 0]), rtol=1e-6)
    # linear scaling changes i=0 (cos(t/k) != cos(t))
    assert not np.allclose(np.asarray(cos_lin[:, 0]),
                           np.asarray(cos0[:, 0]), atol=1e-3)
    # lowest frequency: angle ratio ≈ theta/theta'^((D-2)/D) = 1/k
    t = S - 1
    ang0 = t * theta ** (-(D - 2) / D)
    ang_ntk = float(np.arctan2(np.asarray(sin_ntk[t, -1]),
                               np.asarray(cos_ntk[t, -1])))
    assert abs(ang_ntk - ang0 / k) < 1e-3 * ang0

    import pytest

    with pytest.raises(ValueError, match="rope_scaling_type"):
        rope_frequencies(D, S, theta, k, "cubic")
    # "yarn" is a recipe since PR 30 (tests/test_laguna_lm.py): it asks for
    # the pre-training length it stretches from
    with pytest.raises(ValueError, match="rope_original_max_len"):
        rope_frequencies(D, S, theta, k, "yarn")


def test_rope_ntk_threads_through_model_and_decode():
    """model.rope_scaling_type=ntk: train forward differs from linear at
    the same factor, and the KV-cache decode path matches the train
    forward position-for-position (the decode branches thread the type
    too)."""
    import dataclasses

    import numpy as np

    from pytorch_distributed_train_tpu.generate import (
        build_decode_model,
        init_cache,
    )

    cfg = ModelConfig(name="llama", vocab_size=61, hidden_size=32,
                      num_layers=2, num_heads=4, num_kv_heads=2, mlp_dim=64,
                      max_seq_len=24, rope_scaling=4.0,
                      rope_scaling_type="ntk")
    model = build_model(cfg, PrecisionConfig())
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 61, (1, 10)),
                      jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, ids,
                        train=False)["params"]
    full = model.apply({"params": params}, ids, train=False)

    linear = dataclasses.replace(model, rope_scaling_type="linear")
    assert not np.allclose(np.asarray(full),
                           np.asarray(linear.apply({"params": params}, ids,
                                                   train=False)), atol=1e-3)

    dm = build_decode_model(cfg, PrecisionConfig())
    cache = init_cache(dm, 1)
    logits, cache = dm.apply({"params": params, "cache": cache},
                             ids[:, :6], train=False, mutable=["cache"])
    cache = cache["cache"]
    outs = [np.asarray(logits)]
    for t in range(6, 10):
        logits, cache = dm.apply({"params": params, "cache": cache},
                                 ids[:, t:t + 1], train=False,
                                 mutable=["cache"])
        cache = cache["cache"]
        outs.append(np.asarray(logits))
    stitched = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(stitched, np.asarray(full), rtol=2e-4,
                               atol=2e-4)
