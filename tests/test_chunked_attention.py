"""Chunked (pure-XLA flash-style) attention vs the XLA reference.

impl="chunked" is the path for shapes and backends the Pallas kernel does
not take: same O(S*chunk) memory trade as the flash kernel, plain XLA ops
only. Numerics must match the
dense path to fp32-accumulation tolerance in BOTH directions (values and
gradients) across causal, masked, GQA, and non-divisible shapes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.ops.attention import (
    _chunked_attention, _xla_attention, dot_product_attention,
)


@pytest.fixture(autouse=True)
def _no_attention_env(monkeypatch):
    monkeypatch.delenv("PDTT_ATTENTION_IMPL", raising=False)


def _qkv(B=2, Sq=512, Sk=512, H=4, Hkv=None, D=32, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)) * 0.5, dtype)
    k = jnp.asarray(rng.standard_normal((B, Sk, Hkv or H, D)) * 0.5, dtype)
    v = jnp.asarray(rng.standard_normal((B, Sk, Hkv or H, D)) * 0.5, dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_matches_xla(causal):
    q, k, v = _qkv()
    ref = _xla_attention(q, k, v, causal=causal, mask=None,
                         softmax_dtype=jnp.float32)
    out = _chunked_attention(q, k, v, causal=causal, mask=None,
                             softmax_dtype=jnp.float32, chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_chunked_nondivisible_seq_and_gqa():
    # Sq=300 with chunk=128 → padded final tile; GQA Hkv=2 under H=4
    q, k, v = _qkv(Sq=300, Sk=300, Hkv=2)
    ref = _xla_attention(q, k, v, causal=True, mask=None,
                         softmax_dtype=jnp.float32)
    out = _chunked_attention(q, k, v, causal=True, mask=None,
                             softmax_dtype=jnp.float32, chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_chunked_arbitrary_mask():
    q, k, v = _qkv(Sq=320, Sk=320)
    rng = np.random.default_rng(3)
    mask = jnp.asarray(rng.random((2, 1, 320, 320)) > 0.3)
    # guarantee every row keeps at least one key (degenerate rows differ
    # between dense and chunked only in which uniform garbage they emit)
    mask = mask.at[:, :, :, 0].set(True)
    ref = _xla_attention(q, k, v, causal=False, mask=mask,
                         softmax_dtype=jnp.float32)
    out = _chunked_attention(q, k, v, causal=False, mask=mask,
                             softmax_dtype=jnp.float32, chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_chunked_gradients_match_xla():
    q, k, v = _qkv(Sq=384, Sk=384)

    def loss_with(fn):
        def f(q, k, v):
            out = fn(q, k, v, causal=True, mask=None,
                     softmax_dtype=jnp.float32)
            return jnp.sum(out * out)
        return jax.grad(f, argnums=(0, 1, 2))

    g_ref = loss_with(_xla_attention)(q, k, v)
    g_out = loss_with(
        lambda *a, **kw: _chunked_attention(*a, chunk=128, **kw))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_chunked_bf16_and_dispatch():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = dot_product_attention(q, k, v, causal=True, impl="chunked")
    ref = dot_product_attention(q, k, v, causal=True, impl="xla")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_chunked_small_seq_falls_back_to_dense():
    # Sq <= chunk: single dense tile, exact equality expected
    q, k, v = _qkv(Sq=64, Sk=64)
    out = _chunked_attention(q, k, v, causal=True, mask=None,
                             softmax_dtype=jnp.float32, chunk=256)
    ref = _xla_attention(q, k, v, causal=True, mask=None,
                         softmax_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_chunked_decode_alignment():
    """KV-cache decode shape (Sq=1, long Sk) must keep the causal
    end-alignment the dense path implements."""
    q, k, v = _qkv(Sq=1, Sk=128)
    out = _chunked_attention(q, k, v, causal=True, mask=None,
                             softmax_dtype=jnp.float32, chunk=64)
    ref = _xla_attention(q, k, v, causal=True, mask=None,
                         softmax_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_chunked_peak_memory_is_smaller():
    """Compiled-HLO peak temp memory: chunked must beat dense at long
    sequence (the reason it exists). Uses the CPU backend's memory
    analysis on the value-and-grad program."""
    q, k, v = _qkv(B=1, Sq=2048, Sk=2048, H=2, D=32)

    def make(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True, mask=None,
                              softmax_dtype=jnp.float32) ** 2)
        return jax.jit(jax.grad(f))

    def peak(fn):
        c = make(fn).lower(q, k, v).compile()
        try:
            return c.memory_analysis().temp_size_in_bytes
        except Exception:
            pytest.skip("backend lacks memory_analysis")

    dense = peak(_xla_attention)
    chunked = peak(lambda *a, **kw: _chunked_attention(*a, chunk=256, **kw))
    assert chunked < dense / 2, (chunked, dense)


def test_auto_dispatch_picks_chunked_at_long_seq(monkeypatch):
    from pytorch_distributed_train_tpu.ops import attention as attn

    calls = []
    real = attn._chunked_attention
    monkeypatch.setattr(
        attn, "_chunked_attention",
        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])
    q, k, v = _qkv(B=1, Sq=1024, Sk=1024, H=2, D=8)
    attn.dot_product_attention(q, k, v, causal=True, impl="auto")
    assert calls, "auto at seq>=1024 must route to the chunked path"
    calls.clear()
    q, k, v = _qkv(B=1, Sq=512, Sk=512, H=2, D=8)
    attn.dot_product_attention(q, k, v, causal=True, impl="auto")
    assert not calls, "auto at short seq keeps the dense path"


def test_chunked_broadcastable_2d_mask():
    """The dense path's broadcastable-mask contract holds for chunked."""
    q, k, v = _qkv(Sq=300, Sk=300)
    rng = np.random.default_rng(5)
    mask2d = jnp.asarray(rng.random((300, 300)) > 0.3)
    mask2d = mask2d.at[:, 0].set(True)
    ref = _xla_attention(q, k, v, causal=False, mask=mask2d,
                         softmax_dtype=jnp.float32)
    out = _chunked_attention(q, k, v, causal=False, mask=mask2d,
                             softmax_dtype=jnp.float32, chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sliding_window_matches_explicit_mask():
    """window=W must equal dense attention under an explicit banded mask,
    in both the xla and chunked paths, and the decode cache must agree
    with the full forward for a windowed model."""
    import numpy as np

    from pytorch_distributed_train_tpu.ops.attention import (
        _chunked_attention,
        _xla_attention,
    )

    rng = np.random.default_rng(0)
    B, S, H, D, W = 2, 64, 2, 8, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    pos = np.arange(S)
    band = (pos[:, None] >= pos[None, :]) & (
        pos[:, None] - pos[None, :] < W)
    band_mask = jnp.asarray(band[None, None])

    ref = _xla_attention(q, k, v, causal=False, mask=band_mask,
                         softmax_dtype=jnp.float32)
    xla = _xla_attention(q, k, v, causal=True, mask=None,
                         softmax_dtype=jnp.float32, window=W)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref), atol=1e-6)
    chk = _chunked_attention(q, k, v, causal=True, mask=None,
                             softmax_dtype=jnp.float32, chunk=16, window=W)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(ref), atol=1e-6)

    # windowed llama: KV-cache decode == full forward
    import jax

    from pytorch_distributed_train_tpu.config import (
        ModelConfig, PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.generate import (
        build_decode_model, generate,
    )
    from pytorch_distributed_train_tpu.models.registry import build_model

    cfg = ModelConfig(name="llama", vocab_size=64, hidden_size=32,
                      num_layers=2, num_heads=2, num_kv_heads=2, mlp_dim=64,
                      max_seq_len=48, attention_window=8,
                      attention_impl="xla")
    train_model = build_model(cfg, PrecisionConfig())
    ids = jnp.asarray(rng.integers(0, 64, (1, 20)), jnp.int32)
    variables = train_model.init({"params": jax.random.PRNGKey(0)}, ids,
                                 train=False)
    # (one jitted program a length: op by op, every op of the model
    # compiles again at each of the six lengths)
    forward = jax.jit(lambda v, x: train_model.apply(v, x, train=False))
    logits_full = forward(variables, ids)
    model = build_decode_model(cfg, PrecisionConfig())
    out = generate(model, variables["params"], ids, 6)
    # greedy continuation from the full forward's last logits agrees
    nxt_full = int(jnp.argmax(logits_full[0, -1]))
    assert int(out[0, 20]) == nxt_full
    # and every single-token windowed decode step matches teacher forcing
    for i in range(1, 6):
        logits_i = forward(variables, out[:, : 20 + i])
        assert int(out[0, 20 + i]) == int(jnp.argmax(logits_i[0, -1])), i

    from pytorch_distributed_train_tpu.ops.attention import (
        dot_product_attention,
    )
    import pytest

    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, causal=False, window=4, impl="xla")


def test_gpt2_sliding_window_decode_matches_full_forward():
    import jax
    import numpy as np

    from pytorch_distributed_train_tpu.config import (
        ModelConfig, PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.generate import (
        build_decode_model, generate,
    )
    from pytorch_distributed_train_tpu.models.registry import build_model

    rng = np.random.default_rng(5)
    cfg = ModelConfig(name="gpt2", vocab_size=64, hidden_size=32,
                      num_layers=2, num_heads=2, mlp_dim=64,
                      max_seq_len=48, attention_window=8,
                      attention_impl="xla")
    train_model = build_model(cfg, PrecisionConfig())
    ids = jnp.asarray(rng.integers(0, 64, (1, 20)), jnp.int32)
    variables = train_model.init({"params": jax.random.PRNGKey(0)}, ids,
                                 train=False)
    # (one jitted program a length, as the llama test above)
    forward = jax.jit(lambda v, x: train_model.apply(v, x, train=False))
    logits_full = forward(variables, ids)
    model = build_decode_model(cfg, PrecisionConfig())
    out = generate(model, variables["params"], ids, 4)
    assert int(out[0, 20]) == int(jnp.argmax(logits_full[0, -1]))
    # every SINGLE-TOKEN decode step (the windowed cache mask) must agree
    # with a teacher-forced full forward over the growing sequence
    for i in range(1, 4):
        logits_i = forward(variables, out[:, : 20 + i])
        assert int(out[0, 20 + i]) == int(jnp.argmax(logits_i[0, -1])), i
    # windowed != unwindowed (the band actually changes the computation)
    import dataclasses
    base = build_model(dataclasses.replace(cfg, attention_window=0),
                       PrecisionConfig())
    logits_b = base.apply(variables, ids, train=False)
    assert not np.allclose(np.asarray(logits_full), np.asarray(logits_b))


class TestAutoResolution:
    """impl='auto' is decided by what the code can observe — the platform
    (_on_tpu), the kernel's shape gate and its length threshold — and by
    nothing else: no record file, no platform name in the environment."""

    @staticmethod
    def _resolve(monkeypatch, capsys, on_tpu, shape, impl="auto", **kw):
        from pytorch_distributed_train_tpu.ops import attention as att
        from pytorch_distributed_train_tpu.ops import flash_attention as fa

        calls = []

        def fake_flash(q, k, v, **kwargs):
            calls.append(kwargs)
            return q

        monkeypatch.setattr(att, "_on_tpu", lambda: on_tpu)
        monkeypatch.setattr(fa, "flash_attention", fake_flash)
        att._resolutions_logged.clear()
        q = jnp.zeros(shape, jnp.bfloat16)
        jax.eval_shape(lambda q_: att.dot_product_attention(
            q_, q_, q_, causal=True, impl=impl, **kw), q)
        logged = [ln for ln in capsys.readouterr().err.splitlines()
                  if ln.startswith("[attention]")]
        return calls, logged

    @pytest.mark.parametrize("on_tpu,shape,want", [
        (True, (2, 1024, 12, 64), "pallas"),    # GPT-2 small's shape
        (True, (1, 2048, 8, 128), "pallas"),
        (True, (2, 512, 12, 64), "xla"),        # below the kernel's length
        (True, (2, 1024, 12, 80), "chunked"),   # D the kernel cannot take
        (False, (2, 1024, 12, 64), "chunked"),  # same shape, no TPU
        (False, (2, 256, 4, 64), "xla"),
    ])
    def test_auto_follows_platform_and_shape(self, monkeypatch, capsys,
                                             on_tpu, shape, want):
        calls, logged = self._resolve(monkeypatch, capsys, on_tpu, shape)
        assert len(logged) == 1 and f"impl={want} " in logged[0], logged
        assert bool(calls) == (want == "pallas")
        if calls:  # on a TPU the kernel is never interpreted
            assert calls[0]["interpret"] is False

    def test_no_record_file_or_platform_name_takes_part(
            self, monkeypatch, capsys, tmp_path):
        """The old gate read a committed JSON record and the platform
        name in JAX_PLATFORMS; neither may change the resolution."""
        import os

        from pytorch_distributed_train_tpu.ops import attention as att

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert not os.path.exists(os.path.join(repo, "MOSAIC_PROBE.json"))
        assert not hasattr(att, "_pallas_usable")
        monkeypatch.setenv("MOSAIC_PROBE_PATH", str(tmp_path / "none.json"))
        monkeypatch.setenv("JAX_PLATFORMS", "some_other_backend")
        calls, logged = self._resolve(monkeypatch, capsys, True,
                                      (2, 1024, 12, 64))
        assert calls and "impl=pallas " in logged[0]

    def test_explicit_pallas_interprets_only_off_tpu(self, monkeypatch,
                                                     capsys):
        for on_tpu in (False, True):
            calls, logged = self._resolve(monkeypatch, capsys, on_tpu,
                                          (1, 256, 2, 64), impl="pallas")
            assert calls[0]["interpret"] is (not on_tpu)
            assert f"interpret={not on_tpu}" in logged[0]

    def test_kernel_failure_on_tpu_is_not_swallowed(self, monkeypatch):
        """A kernel the compiler refuses raises out of the dispatch — it
        does not give way to the chunked/XLA path."""
        from pytorch_distributed_train_tpu.ops import attention as att
        from pytorch_distributed_train_tpu.ops import flash_attention as fa

        def refuse(*a, **kw):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        monkeypatch.setattr(att, "_on_tpu", lambda: True)
        monkeypatch.setattr(fa, "flash_attention", refuse)
        q = jnp.zeros((2, 1024, 12, 64), jnp.bfloat16)
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            jax.eval_shape(lambda q_: att.dot_product_attention(
                q_, q_, q_, causal=True), q)

    def test_resolution_is_printed_once_per_signature(self, monkeypatch,
                                                      capsys):
        from pytorch_distributed_train_tpu.ops import attention as att

        att._resolutions_logged.clear()
        q = jnp.zeros((1, 64, 2, 16), jnp.float32)
        for _ in range(3):
            att.dot_product_attention(q, q, q, causal=True)
        err = capsys.readouterr().err
        assert err.count("[attention] impl=xla ") == 1


def test_flash_kernel_runs_as_manual_region_under_a_sharded_mesh(devices8):
    """GSPMD cannot partition a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned" — what the v5e compiler said of the
    data x fsdp train step), so under any mesh of more than one device
    the dispatch wraps the kernel in shard_map over the batch and tensor
    axes. Values and gradients match the XLA path."""
    from pytorch_distributed_train_tpu.config import MeshConfig
    from pytorch_distributed_train_tpu.ops.attention import (
        ContextParallelConfig,
        dot_product_attention,
    )
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2), devices8)
    cp = ContextParallelConfig(mesh=mesh)
    assert not cp.active
    B, S, H, D = 8, 256, 4, 64
    q, k, v = (jax.random.normal(key, (B, S, H, D), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(0), 3))

    def loss(impl, q_, k_, v_):
        out = dot_product_attention(q_, k_, v_, causal=True, impl=impl,
                                    cp=cp)
        return (out * out).sum()

    flash = jax.jit(jax.value_and_grad(
        lambda *a: loss("pallas", *a), argnums=(0, 1, 2)))
    assert "shard_map" in str(jax.make_jaxpr(
        lambda *a: loss("pallas", *a))(q, k, v))
    with mesh:
        l_f, g_f = flash(q, k, v)
        l_x, g_x = jax.jit(jax.value_and_grad(
            lambda *a: loss("xla", *a), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(l_f, l_x, rtol=1e-4)
    for a, b in zip(g_f, g_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("Sq", [512, 600], ids=["divisible", "padded"])
def test_values_of_another_head_dim_than_scores(Sq):
    """Latent attention's shapes: scores 48 deep, values 32. The tiles'
    outputs are put back together at V's head dim, not Q's (a reshape to
    Q's raised at (2, 8192, 32, 192/128) on the chip, PR 26)."""
    q, k, _ = _qkv(Sq=Sq, Sk=Sq, D=48, seed=5)
    v = _qkv(Sq=Sq, Sk=Sq, D=32, seed=6)[2]
    run = lambda f, **kw: jax.value_and_grad(  # noqa: E731
        lambda q, k, v: jnp.sum(f(q, k, v, causal=True, mask=None,
                                  softmax_dtype=jnp.float32, **kw) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    (a, ga), (b, gb) = run(_chunked_attention, chunk=128), run(_xla_attention)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    for x, y in zip(ga, gb):
        assert x.shape == y.shape
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5,
                                   rtol=2e-5)
