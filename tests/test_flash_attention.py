"""Pallas flash attention vs XLA reference, interpret mode on CPU
(SURVEY §5.2: "Pallas kernels → interpret=True mode vs XLA reference
implementation in tests")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_train_tpu.ops.attention import _xla_attention
from pytorch_distributed_train_tpu.ops.flash_attention import (
    flash_attention,
    supported,
)


@pytest.fixture(autouse=True)
def _no_attention_env(monkeypatch):
    """The PDTT_ATTENTION_IMPL kill switch overrides even explicit impl
    args; with it exported the pallas-vs-xla tests would compare XLA to
    itself. Scrub it for every test in this module."""
    monkeypatch.delenv("PDTT_ATTENTION_IMPL", raising=False)


def _make_qkv(B=2, S=256, H=2, D=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B, S, H, D)) * 0.5, dtype
    )
    return mk(), mk(), mk()


def _xla(q, k, v, causal):
    return _xla_attention(q, k, v, causal=causal, mask=None,
                          softmax_dtype=jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal):
    q, k, v = _make_qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _xla(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_xla(causal):
    q, k, v = _make_qkv(B=1, S=256, H=2, D=64, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3,
            err_msg=f"d{name} mismatch",
        )


def test_multi_block_seq():
    # exercises the online-softmax accumulation across 4 KV blocks
    q, k, v = _make_qkv(B=1, S=512, H=1, D=64, seed=5)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _xla(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_bf16_inputs():
    q, k, v = _make_qkv(B=1, S=256, H=2, D=64, seed=7, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = _xla(q, k, v, False)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_windowed_forward_matches_xla():
    """Sliding window in the kernel (band mask within tiles + out-of-band
    block skip) vs the XLA reference band."""
    q, k, v = _make_qkv(B=1, S=512, H=2, D=64, seed=9)
    for W in (32, 100, 511):
        out = flash_attention(q, k, v, causal=True, window=W, interpret=True)
        ref = _xla_attention(q, k, v, causal=True, mask=None,
                             softmax_dtype=jnp.float32, window=W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=f"W={W}")


def test_windowed_gradients_match_xla():
    q, k, v = _make_qkv(B=1, S=256, H=2, D=64, seed=13)
    W = 64

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, causal=True, window=W, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(_xla_attention(
        a, b, c, causal=True, mask=None, softmax_dtype=jnp.float32,
        window=W) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


# (id, S, H, Hkv, block_q, major, block_k, causal, window): explicit tile
# sizes, so every loop shape of the two-level tiling is walked in interpret
# mode: several majors a row, tiles wider than tall and the reverse, a
# window inside one tile and one that spans majors, GQA's revisit axis.
TILED_CASES = [
    ("s1024_tiles256", 1024, 1, 1, 256, 1024, 256, True, 0),
    ("s1024_tiles128", 1024, 1, 1, 128, 1024, 128, True, 0),
    ("s1024_major512_q256_k128", 1024, 1, 1, 256, 512, 128, True, 0),
    ("s512_q128_k256_window200", 512, 2, 2, 128, 512, 256, True, 200),
    ("s512_gqa_rep4", 512, 4, 1, 256, 256, 128, True, 0),
    ("s512_window_inside_a_tile", 512, 2, 2, 128, 256, 128, True, 50),
    ("s512_window_wider_than_major", 512, 2, 2, 128, 256, 128, True, 300),
    ("s512_noncausal", 512, 2, 1, 128, 256, 128, False, 0),
    ("s384_rule", 384, 2, 2, None, None, None, True, 0),
]


@pytest.mark.parametrize("name,S,H,Hkv,block_q,major,block_k,causal,window",
                         TILED_CASES, ids=[c[0] for c in TILED_CASES])
def test_tiled_kernels_match_xla(name, S, H, Hkv, block_q, major, block_k,
                                 causal, window):
    """Outputs and all three gradients of the tiled kernels against the
    XLA reference (GQA: the reference expands K/V inside the loss)."""
    q, _, _ = _make_qkv(B=1, S=S, H=H, D=64, seed=31)
    _, k, v = _make_qkv(B=1, S=S, H=Hkv, D=64, seed=37)
    rep = H // Hkv

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               block_k_major=major, interpret=True)

    def ref(q, k, v):
        return _xla_attention(q, jnp.repeat(k, rep, axis=2),
                              jnp.repeat(v, rep, axis=2), causal=causal,
                              mask=None, softmax_dtype=jnp.float32,
                              window=window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-3, err_msg=f"d{n} mismatch")


@pytest.mark.parametrize("window", [0, 100])
def test_chunk_entry_rotated_positions_match_xla(window):
    """The ring's entry with positions as a zigzag ring hands them over:
    two half-chunks out of order on both sides, so score tiles are entered
    or skipped by their positions' min/max, and some rows see no key at
    all: those return zeros and lse = NEG_INF."""
    from pytorch_distributed_train_tpu.ops.flash_attention import (
        NEG_INF,
        flash_attention_chunk,
    )

    S, half = 512, 256
    q, _, _ = _make_qkv(B=1, S=S, H=4, D=64, seed=41)
    _, k, v = _make_qkv(B=1, S=S, H=2, D=64, seed=43)
    ar = jnp.arange(half, dtype=jnp.int32)
    q_pos = jnp.concatenate([ar + 256, ar + 1536])
    kv_pos = jnp.concatenate([ar + 1024, ar + 384])
    d = q_pos[:, None] - kv_pos[None, :]
    keep = d >= 0
    if window:
        keep &= d < window
    valid = np.asarray(keep.any(axis=1))
    assert valid.any() and not valid.all()
    rows = jnp.asarray(valid, jnp.float32)[None, :, None, None]

    def flash(q, k, v):
        o, lse = flash_attention_chunk(
            q, k, v, q_pos, kv_pos, causal=True, window=window, block_q=128,
            block_k=128, block_k_major=256, interpret=True)
        return o, lse

    def ref(q, k, v):
        return _xla_attention(q, jnp.repeat(k, 2, axis=2),
                              jnp.repeat(v, 2, axis=2), causal=False,
                              mask=keep[None, None], softmax_dtype=jnp.float32)

    o, lse = flash(q, k, v)
    np.testing.assert_allclose(np.asarray(o)[:, valid],
                               np.asarray(ref(q, k, v))[:, valid],
                               atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(o[:, ~valid]).max()) == 0.0
    assert float(lse[:, :, ~valid].max()) == np.float32(NEG_INF)
    assert float(lse[:, :, valid].min()) > NEG_INF / 2
    gf = jax.grad(lambda *a: jnp.sum((flash(*a)[0] * rows) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum((ref(*a) * rows) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-3, err_msg=f"d{n} mismatch")


def test_tile_plan_counts_and_tile_ranges():
    """tile_plan's counts at the shapes PERF.md quotes, and the kernels'
    own tile ranges (what a grid step enters, and where it masks) against
    the same classification of every tile, for both sweep directions."""
    from pytorch_distributed_train_tpu.ops import flash_attention as fa

    assert fa.tile_plan(1024, 256, 256, causal=True) == (16, 10, 4)
    assert fa.tile_plan(1024, 256, 256, causal=False) == (16, 16, 0)
    assert fa.tile_plan(1024, 128, 128, causal=True) == (64, 36, 8)
    assert fa.tile_plan(1024, 512, 1024, causal=True) == (2, 2, 2)  # PR 24's
    assert fa.tile_plan(2048, 256, 256, causal=True, window=512) == (64, 21, 14)
    # the rule at the benchmark's shape and at the long-sequence shape
    assert fa.tile_sizes(1024, 1024, 64, 2) == (512, 512, 1024, 1024)
    assert fa.tile_sizes(2048, 2048, 128, 2) == (512, 512, 2048, 2048)
    assert fa.tile_sizes(384, 384, 64, 4) == (128, 128, 384, 384)
    assert fa.tile_sizes(1024, 1024, 256, 4) == (512, 512, 512, 512)

    def kinds(d_min, d_max, causal, window):
        if (causal and d_max < 0) or (window and d_min >= window):
            return "skip"
        return "mask" if ((causal and d_min < 0)
                          or (window and d_max >= window)) else "plain"

    def from_ranges(ranges, n):
        lo, a, b, hi = (int(x) for x in ranges)
        return ["mask" if lo <= t < a or b <= t < hi else
                "plain" if a <= t < b else "skip" for t in range(n)]

    S = 1024
    for bq, bk, major in ((256, 256, 1024), (128, 256, 512), (256, 128, 512),
                          (512, 128, 1024)):
        for causal, window in ((True, 0), (True, 50), (True, 300),
                               (True, 700), (False, 0), (False, 130)):
            executed = masked = 0
            for q0 in range(0, S, bq):       # forward / dQ: a Q tile's KV tiles
                for k0 in range(0, S, major):
                    n = major // bk
                    got = from_ranges(fa._kv_ranges(q0 - k0, n, bq, bk, causal,
                                                    window), n)
                    want = [kinds(q0 - (k0 + j * bk + bk - 1),
                                  q0 + bq - 1 - (k0 + j * bk), causal, window)
                            for j in range(n)]
                    assert got == want, (bq, bk, causal, window, q0, k0)
                    executed += sum(w != "skip" for w in want)
                    masked += want.count("mask")
            assert fa.tile_plan(S, bq, bk, causal=causal, window=window) == (
                (S // bq) * (S // bk), executed, masked)
            for k0 in range(0, S, bk):       # dK/dV: a KV tile's Q tiles
                for q0 in range(0, S, major):
                    n = major // bq
                    got = from_ranges(fa._q_ranges(k0 - q0, n, bq, bk, causal,
                                                   window), n)
                    want = [kinds(q0 + i * bq - (k0 + bk - 1),
                                  q0 + i * bq + bq - 1 - k0, causal, window)
                            for i in range(n)]
                    assert got == want, (bq, bk, causal, window, k0, q0)


def test_chunk_entry_contract():
    """flash_attention_chunk: the ring inner kernel's (o, lse) contract —
    diagonal chunk == causal self-attention; all-future chunk returns
    o=0 / lse=NEG_INF (zero weight under the merge rule)."""
    from pytorch_distributed_train_tpu.ops.flash_attention import (
        flash_attention_chunk,
    )

    q, k, v = _make_qkv(B=1, S=256, H=2, D=64, seed=17)
    pos = jnp.arange(256, dtype=jnp.int32)
    o, lse = flash_attention_chunk(q, k, v, pos, pos, causal=True,
                                   interpret=True)
    ref = _xla(q, k, v, True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert lse.shape == (1, 2, 256)

    o_f, lse_f = flash_attention_chunk(q, k, v, pos, pos + 256, causal=True,
                                       interpret=True)
    assert float(jnp.abs(o_f).max()) == 0.0
    assert float(lse_f.max()) < -1e29


def test_chunk_merge_equals_full_attention_with_grads():
    """Two merged chunks (flash merge rule) == one attention over the
    concatenated keys, through the backward — this exercises the lse
    cotangent folding (delta' = delta − dlse) that ring attention relies
    on."""
    from pytorch_distributed_train_tpu.ops.flash_attention import (
        flash_attention_chunk,
    )
    from pytorch_distributed_train_tpu.ops.ring_attention import _merge

    S = 256
    q, k1, v1 = _make_qkv(B=1, S=S, H=2, D=64, seed=19)
    _, k2, v2 = _make_qkv(B=1, S=S, H=2, D=64, seed=23)
    pos = jnp.arange(S, dtype=jnp.int32)

    def merged(a, b1, c1, b2, c2):
        o1, l1 = flash_attention_chunk(a, b1, c1, pos + S, pos,
                                       causal=True, interpret=True)
        o2, l2 = flash_attention_chunk(a, b2, c2, pos + S, pos + S,
                                       causal=True, interpret=True)
        o, _ = _merge(o1, l1, o2, l2)
        return o

    def ref(a, b1, c1, b2, c2):
        kk = jnp.concatenate([b1, b2], axis=1)
        vv = jnp.concatenate([c1, c2], axis=1)
        # Sq < Sk: _xla_attention aligns ends, i.e. q_pos = S..2S-1 — the
        # same layout as the merged chunks above.
        return _xla_attention(a, kk, vv, causal=True, mask=None,
                              softmax_dtype=jnp.float32)

    om = merged(q, k1, v1, k2, v2)
    orf = ref(q, k1, v1, k2, v2)
    np.testing.assert_allclose(np.asarray(om), np.asarray(orf),
                               atol=2e-5, rtol=2e-5)

    gm = jax.grad(lambda *a: jnp.sum(merged(*a) ** 2),
                  argnums=(0, 1, 2, 3, 4))(q, k1, v1, k2, v2)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                  argnums=(0, 1, 2, 3, 4))(q, k1, v1, k2, v2)
    for a, b, name in zip(gm, gr, ["q", "k1", "v1", "k2", "v2"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_dispatch_windowed_pallas_impl():
    """impl='pallas' with a window runs the kernel (the old refusal is
    gone) and matches the windowed XLA path."""
    from pytorch_distributed_train_tpu.ops.attention import dot_product_attention

    q, k, v = _make_qkv(B=1, S=256, H=2, D=64, seed=29)
    out = dot_product_attention(q, k, v, causal=True, window=64,
                                impl="pallas")
    ref = dot_product_attention(q, k, v, causal=True, window=64, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_resolution_line_prints_the_tile_plan(capsys):
    """The dispatch's once-per-shape line says how many of a head's score
    tiles the kernel enters and masks, and they are tile_plan's for the
    call's shape under the kernel's own tile rule."""
    from pytorch_distributed_train_tpu.ops import attention as attn
    from pytorch_distributed_train_tpu.ops import flash_attention as fa

    q = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)
    attn._resolutions_logged.clear()
    jax.eval_shape(lambda a: attn.dot_product_attention(
        a, a, a, causal=True, impl="pallas"), q)
    line = capsys.readouterr().err.strip().splitlines()[-1]
    plan = fa.call_plan(q, q, causal=True)
    assert line.startswith("[attention] impl=pallas q=(1, 1024, 2, 64)")
    assert line.endswith(
        f"tiles={plan.executed}/{plan.total} masked={plan.masked}")
    assert plan == (4, 3, 2)


def test_dispatch_pallas_impl_covers_gqa_expansion():
    """impl='pallas' runs the real dispatch path (GQA native in-kernel) in
    interpret mode on CPU — the CI seam for lines only a TPU would hit."""
    from pytorch_distributed_train_tpu.ops.attention import dot_product_attention

    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    out = dot_product_attention(q, k, v, causal=True, impl="pallas")
    ref = dot_product_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_native_gqa_matches_expanded_reference():
    """GQA without HBM expansion (r4 kernel follow-up): the kernel's
    b // rep KV index_map must reproduce the expand-first math exactly —
    forward AND all three grads (dK/dV accumulate over the rep query
    heads sharing each KV tile via the revisit grid axis)."""
    rep = 2
    q, _, _ = _make_qkv(B=2, S=256, H=4, D=64, seed=5)
    _, k, v = _make_qkv(B=2, S=256, H=2, D=64, seed=7)

    def expand(x):
        return jnp.repeat(x, rep, axis=2)

    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        ref = _xla(q, expand(k), expand(v), causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        # expansion INSIDE the loss → grad wrt the unexpanded k/v is the
        # group-sum of the expanded grads, exactly what native GQA owes
        return jnp.sum(_xla(q, expand(k), expand(v), True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3,
            err_msg=f"d{name} mismatch (native GQA)",
        )


def test_flash_rejects_invalid_gqa_ratio():
    q, _, _ = _make_qkv(B=1, S=256, H=4, D=64)
    _, k, v = _make_qkv(B=1, S=256, H=3, D=64)
    with pytest.raises(ValueError, match="GQA ratio"):
        flash_attention(q, k, v, interpret=True)


def test_supported_gates():
    q, k, v = _make_qkv(S=256, D=64)
    assert supported(q, k, v, causal=False, mask=None)
    assert not supported(q, k, v, causal=False, mask=jnp.ones((1, 1, 1, 256)))
    q2, k2, v2 = _make_qkv(S=100, D=64)  # S not block-divisible
    assert not supported(q2, k2, v2, causal=False, mask=None)
    q3, k3, v3 = _make_qkv(S=256, D=48)  # D not lane-aligned
    assert not supported(q3, k3, v3, causal=False, mask=None)


def test_default_impl_override(monkeypatch):
    """Backend selection: ModelConfig.attention_impl threads into the module
    tree (no process-global state); set_default_impl is the operator-level
    control for impl='auto' callers; PDTT_ATTENTION_IMPL is the kill switch
    that beats everything, including explicit impl args."""
    from pytorch_distributed_train_tpu.config import ModelConfig, PrecisionConfig
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.ops import attention as attn

    monkeypatch.delenv("PDTT_ATTENTION_IMPL", raising=False)
    orig = attn._default_impl
    try:
        attn.set_default_impl("xla")
        q, k, v = _make_qkv(B=1, S=2048, H=2, D=128)  # supported+profitable
        out = attn.dot_product_attention(q, k, v, causal=True)  # impl="auto"
        ref = attn._xla_attention(q, k, v, causal=True, mask=None,
                                  softmax_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

        # the config knob is a static module attr — two models with
        # different backends coexist, nothing global mutates
        tiny = dict(vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
                    mlp_dim=64, max_seq_len=16)
        m_xla = build_model(ModelConfig(name="llama", **tiny,
                                        attention_impl="xla"),
                            PrecisionConfig())
        m_auto = build_model(ModelConfig(name="llama", **tiny),
                             PrecisionConfig())
        assert m_xla.attn_impl == "xla" and m_auto.attn_impl == "auto"
        assert attn._default_impl == "xla"  # untouched by builds

        # env var beats the setter, an explicit impl arg, and the heuristic
        monkeypatch.setenv("PDTT_ATTENTION_IMPL", "xla")
        attn.set_default_impl("pallas")
        assert attn._resolve_default_impl() == "xla"
        out_env = attn.dot_product_attention(q, k, v, causal=True,
                                             impl="pallas")
        np.testing.assert_array_equal(np.asarray(out_env), np.asarray(ref))

        monkeypatch.setenv("PDTT_ATTENTION_IMPL", "flash")
        with pytest.raises(ValueError, match="PDTT_ATTENTION_IMPL"):
            attn.dot_product_attention(q, k, v, causal=True)
        monkeypatch.delenv("PDTT_ATTENTION_IMPL")

        with pytest.raises(ValueError, match="auto|xla|pallas"):
            attn.set_default_impl("nope")
    finally:
        attn._default_impl = orig


@pytest.mark.parametrize("d_qk,d_v", [(192, 128), (128, 64), (256, 128)],
                         ids=["mla_192_128", "128_64", "256_128"])
def test_values_of_another_head_dim_than_scores_match_xla(d_qk, d_v):
    """Latent attention: Q and K carry plain and rotated dims (192), V only
    plain ones (128). V and O keep their own head dim through all three
    kernels. Several KV blocks a step (tiles of 128 in a major block of
    256), so the running state is exercised."""
    rng = np.random.default_rng(11)
    mk = lambda d: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 512, 2, d)) * 0.5, jnp.float32)
    q, k, v, w = mk(d_qk), mk(d_qk), mk(d_v), mk(d_v)
    kernel = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=True, block_q=128, block_k=128,
        block_k_major=256)
    assert supported(q, k, v, causal=True, mask=None)
    out = kernel(q, k, v)
    assert out.shape == v.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(_xla(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(_xla(*a, True) * w),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-5, err_msg=name)


def test_head_dims_the_kernels_cannot_take_stay_unsupported():
    mk = lambda d: jnp.zeros((1, 256, 2, d), jnp.float32)  # noqa: E731
    assert not supported(mk(32), mk(32), mk(32), causal=True, mask=None)
    assert not supported(mk(96), mk(96), mk(96), causal=True, mask=None)
    assert not supported(mk(192), mk(192), mk(96), causal=True, mask=None)
    assert supported(mk(192), mk(192), mk(128), causal=True, mask=None)
    with pytest.raises(ValueError, match="do not fit"):
        flash_attention(mk(192), mk(128), mk(128), interpret=True)
