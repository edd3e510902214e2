"""Pallas flash attention vs XLA reference, interpret mode on CPU
(SURVEY §5.2: "Pallas kernels → interpret=True mode vs XLA reference
implementation in tests")."""

import functools

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


def _record_backward_forms(monkeypatch):
    """Which backward ``_bwd`` hands a call to, as it is traced."""
    from pytorch_distributed_train_tpu.ops import flash_attention as fa

    taken = []
    for form in ("fused", "split"):
        inner = getattr(fa, f"_bwd_{form}")

        def recorder(*a, _inner=inner, _form=form, **kw):
            taken.append(_form)
            return _inner(*a, **kw)

        monkeypatch.setattr(fa, f"_bwd_{form}", recorder)
    return taken


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
    # four major blocks, so K's and V's index maps clamp (block_map): steps
    # above the diagonal and, under a window, below the band fetch nothing
    ("s1024_four_majors_causal_gqa", 1024, 2, 1, 128, 256, 128, True, 0),
    ("s1024_four_majors_window300", 1024, 1, 1, 128, 256, 128, True, 300),
    ("s1024_four_majors_window_alone", 1024, 1, 1, 256, 256, 128, False,
     300),
]


def _ref_attention(q, k, v, *, causal, window):
    """The XLA reference, K and V expanded to Q's heads; a window with no
    causal mask (every later key, ``window`` earlier ones: the kernels
    take it, `_xla_attention` has no word for it) as an explicit mask."""
    rep = q.shape[2] // k.shape[2]
    mask = None
    if window and not causal:
        pos = jnp.arange(q.shape[1])
        mask = (pos[:, None] - pos[None, :] < window)[None, None]
    return _xla_attention(q, jnp.repeat(k, rep, axis=2),
                          jnp.repeat(v, rep, axis=2), causal=causal,
                          mask=mask, softmax_dtype=jnp.float32,
                          window=window)


@pytest.mark.parametrize("name,S,H,Hkv,block_q,major,block_k,causal,window",
                         TILED_CASES, ids=[c[0] for c in TILED_CASES])
def test_tiled_kernels_match_xla(name, S, H, Hkv, block_q, major, block_k,
                                 causal, window):
    """Outputs and all three gradients of the tiled kernels against the
    XLA reference (GQA: the reference expands K/V inside the loss)."""
    q, _, _ = _make_qkv(B=1, S=S, H=H, D=64, seed=31)
    _, k, v = _make_qkv(B=1, S=S, H=Hkv, D=64, seed=37)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               block_k_major=major, interpret=True)

    ref = functools.partial(_ref_attention, causal=causal, window=window)

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


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("window", [0, 100])
def test_chunk_entry_rotated_positions_match_xla(monkeypatch, window,
                                                 backward):
    """The ring's entry with positions as a zigzag ring hands them over:
    two half-chunks out of order on both sides, so score tiles are entered
    or skipped by their positions' min/max, and some rows see no key at
    all: those return zeros and lse = NEG_INF. Its backward is the fused
    kernel too (every chunk of a major block under its own predicate, dQ
    in its scratch), and past the VMEM budget the two kernels."""
    from pytorch_distributed_train_tpu.ops import flash_attention as fa
    from pytorch_distributed_train_tpu.ops.flash_attention import (
        NEG_INF,
        flash_attention_chunk,
    )

    if backward == "split":
        monkeypatch.setattr(fa, "FUSED_RESIDENT_BYTES", 0)
    taken = _record_backward_forms(monkeypatch)

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
    assert taken == [backward]
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


# ------------------------------------------------- the backward's two forms

# (id, S, H, Hkv, D, Dv, causal, window, tiles): the fused kernel's cases.
# tiles None is the rule's: at S 1024 ONE major block (the `direct` form:
# dQ written by its step, no running state), at S 4096 two of 2048 (dQ in
# its scratch across them, the accumulators' first index traced).
BACKWARD_CASES = [
    ("mha_direct_s1024", 1024, 2, 2, 64, 64, True, 0, None),
    ("mha_noncausal", 512, 2, 2, 64, 64, False, 0, (128, 128, 256)),
    ("gqa_rep2_d128", 512, 4, 2, 128, 128, True, 0, (128, 128, 256)),
    ("gqa_rep3", 512, 6, 2, 64, 64, True, 0, (256, 128, 256)),
    ("gqa_rep8_over_1_noncausal", 256, 8, 1, 64, 64, False, 0,
     (128, 128, 128)),
    ("window_200_gqa_rep2", 512, 4, 2, 64, 64, True, 200, (128, 128, 256)),
    ("mla_192_128", 512, 2, 2, 192, 128, True, 0, (128, 128, 256)),
    ("s4096_two_majors_of_the_rule", 4096, 1, 1, 64, 64, True, 0, None),
    # four major blocks on both sides (a split dK/dV streams Q's): the
    # clamped index maps of all four calls, causal, window and both
    ("four_majors_causal_gqa_rep2", 1024, 4, 2, 64, 64, True, 0,
     (128, 128, 256)),
    ("four_majors_window300", 1024, 2, 2, 64, 64, True, 300,
     (128, 128, 256)),
    # the 16k window/full cell's call in small (mellum2-1chip-ep4-s16k: a
    # band under 8 query heads a KV head, several major blocks both sides)
    ("four_majors_window300_gqa_rep8", 1024, 8, 1, 64, 64, True, 300,
     (128, 128, 256)),
    ("four_majors_window_alone", 1024, 2, 2, 64, 64, False, 300,
     (256, 128, 256)),
    ("four_majors_mla_192_128", 1024, 1, 1, 192, 128, True, 0,
     (256, 128, 256)),
]


@pytest.mark.parametrize("form", ["fused", "split"])
@pytest.mark.parametrize("name,S,H,Hkv,D,Dv,causal,window,tiles",
                         BACKWARD_CASES, ids=[c[0] for c in BACKWARD_CASES])
def test_backward_forms_match_xla(monkeypatch, name, S, H, Hkv, D, Dv,
                                  causal, window, tiles, form):
    """All three gradients against the XLA reference from the ONE fused
    backward kernel, and from the two kernels a call past the VMEM budget
    keeps (the budget set to nothing here: the rule's own function decides,
    so the same call takes the other path)."""
    from pytorch_distributed_train_tpu.ops import flash_attention as fa

    if form == "split":
        monkeypatch.setattr(fa, "FUSED_RESIDENT_BYTES", 0)
    taken = _record_backward_forms(monkeypatch)
    rng = np.random.default_rng(53)
    mk = lambda h, d: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, S, h, d)) * 0.5, jnp.float32)
    q, k, v, w = mk(H, D), mk(Hkv, D), mk(Hkv, Dv), mk(H, Dv)
    bq, bk, major = tiles or (None, None, None)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk, block_k_major=major,
                               interpret=True)

    ref = functools.partial(_ref_attention, causal=causal, window=window)

    gf = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    assert taken == [form]
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        assert a.shape == b.shape, n
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{n} mismatch")


def test_backward_past_the_vmem_budget_keeps_two_kernels_and_the_gradients(
        monkeypatch):
    """The shape rule, not a knob: `backward_plan` is fused while a KV
    head's dK and dV (float32 accumulators and the twice-buffered output
    blocks) fit FUSED_RESIDENT_BYTES, every benchmark cell's shape does,
    and the first shape past it takes the two kernels; at one call's shape
    both forms return the same gradients, bfloat16 operands as the cells'."""
    from pytorch_distributed_train_tpu.ops import flash_attention as fa

    for Sk, D, Dv, rep in ((1024, 64, 64, 1), (4096, 128, 128, 1),
                           (8192, 128, 128, 6), (8192, 128, 128, 9),
                           (8192, 128, 128, 8), (8192, 192, 128, 1)):
        plan = fa.backward_plan(Sk, D, Dv, 2, rep=rep)
        held = Sk * (D + Dv) * 8  # dK, dV: float32 sums, two output blocks
        tile = rep * 512 * (4 * (2 * D + Dv) + 4 * D)  # Q, dO, dQ; dQ's sum
        assert plan.fused and plan.resident == held + tile, plan
    for past in (fa.backward_plan(16384, 128, 128, 2),
                 fa.backward_plan(8192, 128, 128, 2, rep=32)):
        assert not past.fused and str(past).startswith("bwd=split resident=")
    assert str(fa.backward_plan(1024, 64, 64, 2)) == \
        "bwd=fused resident=1.6MB"

    q, _, _ = _make_qkv(B=1, S=512, H=4, D=64, seed=61, dtype=jnp.bfloat16)
    _, k, v = _make_qkv(B=1, S=512, H=2, D=64, seed=67, dtype=jnp.bfloat16)
    grads = {}
    for form, budget in (("fused", fa.FUSED_RESIDENT_BYTES),
                         ("split", fa.backward_plan(
                             512, 64, 64, 2, rep=2, block_q=128).resident
                          - 1)):
        monkeypatch.setattr(fa, "FUSED_RESIDENT_BYTES", budget)
        taken = _record_backward_forms(monkeypatch)
        grads[form] = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, block_q=128, block_k=128, block_k_major=256,
            interpret=True).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(
                q, k, v)
        assert taken[-1:] == [form], taken
    for a, b, n in zip(grads["fused"], grads["split"], "qkv"):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-2,
            rtol=2e-2, err_msg=f"d{n}: fused against split")


# (id, S, window, D): the cells' attention shapes under the tile rule
FUSED_PLAN_CASES = [("gpt2_small", 1024, 0, 64), ("looped", 4096, 0, 128),
                    ("full_8k", 8192, 0, 128), ("window_512", 8192, 512, 128),
                    ("mla_8k", 8192, 0, 192)]


@pytest.mark.parametrize("name,S,window,D", FUSED_PLAN_CASES,
                         ids=[c[0] for c in FUSED_PLAN_CASES])
def test_fused_backward_enters_the_tiles_of_tile_plan(monkeypatch, name, S,
                                                      window, D):
    """What the fused kernel's own trace hands the dispatch (one offset a
    (Q tile, major block) pair of its grid, the segments of each): the
    score tiles it enters and those it masks are `tile_plan`'s for the
    call, and the forward's."""
    from pytorch_distributed_train_tpu.ops import flash_attention as fa

    calls = []
    monkeypatch.setattr(
        fa, "_static_dispatch",
        lambda off, offsets, segments_of, update: calls.append(
            [segments_of(o) for o in offsets]))
    x = jax.ShapeDtypeStruct((1, S, 2, D), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda *a: flash_attention(
        *a, causal=True, window=window, interpret=True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), x, x, x)
    assert len(calls) == 2  # the forward's and the ONE backward kernel's
    block_q, block_k, _, _ = fa.tile_sizes(S, S, D, 2)
    plan = fa.tile_plan(S, block_q, block_k, causal=True, window=window)
    assert plan == fa.call_plan(x, x, causal=True, window=window)
    for segs in calls:
        entered = sum((c1 - c0) // block_k for s in segs for c0, c1, _ in s)
        masked = sum((c1 - c0) // block_k for s in segs for c0, c1, m in s
                     if m is not None)
        assert (entered, masked) == (plan.executed, plan.masked)


# (id, S, D, (block_q, block_k, major) or the rule's, causal, window,
# FetchPlan or None where only the properties are asserted): the cells'
# attention shapes (gpt2s one chip and dp4, looped, window/full's two
# kinds and the head-share cell's NoPE layer, latent attention's) and
# small ones: a window whose band starts past block 0, one alone, no mask.
BLOCK_MAP_CASES = [
    ("gpt2_small", 1024, 64, None, True, 0, (2, 2, 1)),
    ("looped", 4096, 128, None, True, 0, (16, 12, 8)),
    ("full_8k", 8192, 128, None, True, 0, (64, 40, 36)),
    ("window_512", 8192, 128, None, True, 512, (64, 19, 4)),
    ("mla_8k", 8192, 192, None, True, 0, (128, 72, 70)),
    ("small_causal", 1024, 64, (128, 128, 256), True, 0, (32, 20, 18)),
    ("small_window300", 1024, 64, (128, 128, 256), True, 300, None),
    ("small_window_inside_a_major", 1024, 64, (128, 128, 256), True, 50,
     None),
    ("small_window_alone", 1024, 64, (256, 128, 256), False, 300, None),
    ("small_tall_tiles", 1024, 64, (512, 128, 256), True, 200, None),
    ("small_no_mask", 1024, 64, (128, 128, 256), False, 0, (32, 32, 32)),
    ("small_one_major", 512, 64, (128, 128, 512), True, 100, (4, 4, 1)),
]


@pytest.mark.parametrize("streams", ["kv", "q"])
@pytest.mark.parametrize("name,S,D,tiles,causal,window,want",
                         BLOCK_MAP_CASES, ids=[c[0] for c in BLOCK_MAP_CASES])
def test_block_map_fetches_what_the_grid_enters(name, S, D, tiles, causal,
                                                window, want, streams):
    """Each kernel's grid walked in plain Python through the index-map
    helper its `pallas_call` uses (`block_map`: "kv" the forward, the fused
    backward and the split dQ; "q" the split dK/dV, Q's side streamed under
    a KV tile). (a) A step holding a score tile that `tile_plan`'s own rule
    enters is given its own block; an empty step one its tile enters.
    (b) The index changes along the walk are `FetchPlan.fetched`, and the
    numbers PERF.md quotes. (c) One major block, or no mask: the identity.
    And traced indices, as an index map gets them, give the walk's blocks."""
    from pytorch_distributed_train_tpu.ops import flash_attention as fa

    t = fa.tile_sizes(S, S, D, 2, **dict(zip(
        ("block_q", "block_k", "block_k_major"), tiles or ())))
    bmap = fa.block_map(S, S, t, causal=causal, window=window,
                        streams=streams)
    tile, major = (t.block_q, t.major_k) if streams == "kv" else \
        (t.block_k, t.major_q)
    n_tiles, n_major = S // tile, S // major
    entered = set()  # steps holding a score tile of tile_plan's rule
    for q0 in range(0, S, t.block_q):
        for k0 in range(0, S, t.block_k):
            d_max, d_min = q0 + t.block_q - 1 - k0, q0 - (k0 + t.block_k - 1)
            if not ((causal and d_max < 0) or (window and d_min >= window)):
                entered.add((q0 // tile, k0 // major) if streams == "kv"
                            else (k0 // tile, q0 // major))
    walk = [bmap(i, j) for i in range(n_tiles) for j in range(n_major)]
    assert all(isinstance(b, int) and 0 <= b < n_major for b in walk)
    for i in range(n_tiles):
        for j in range(n_major):
            if (i, j) in entered:
                assert bmap(i, j) == j, (i, j)
            else:
                assert (i, bmap(i, j)) in entered, (i, j)
    plan = bmap.plan()
    changes = 1 + sum(a != b for a, b in zip(walk, walk[1:]))
    assert plan == (n_tiles * n_major, len(entered), changes)
    assert plan.entered <= plan.steps and plan.fetched <= plan.steps
    if want is not None:
        assert plan == want  # the same numbers on both sides: S x S
    one_block_or_no_mask = n_major == 1 or not (causal or window)
    assert bmap.identity == (len(entered) == n_tiles * n_major)
    if one_block_or_no_mask:
        assert bmap.identity
    if bmap.identity:
        assert walk == [j for _ in range(n_tiles) for j in range(n_major)]
        marker = object()
        assert bmap(0, marker) is marker  # the compiled map is j itself
    ii, jj = jnp.meshgrid(jnp.arange(n_tiles), jnp.arange(n_major),
                          indexing="ij")
    traced = jax.jit(bmap)(ii, jj)
    assert traced.dtype == jnp.int32
    assert np.asarray(traced).ravel().tolist() == walk


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
    call's shape under the kernel's own tile rule; then a head's grid steps
    (entered / all) and the K and V blocks its index maps fetch, by the map
    the calls use; then the backward's form, by the function the kernel's
    backward asks. At latent attention's shape, eight major blocks a Q
    tile, the line says 72 of 128 steps and 70 fetches for the parent's
    128."""
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
        f"tiles={plan.executed}/{plan.total} masked={plan.masked} "
        f"{fa.call_fetch_plan(q, q, causal=True)} "
        f"{fa.backward_plan(1024, 64, 64, 2)}")
    assert line.endswith(" steps=2/2 fetches=1 bwd=fused resident=1.6MB")
    assert plan == (4, 3, 2)

    q, v = (jax.ShapeDtypeStruct((1, 8192, 2, d), jnp.bfloat16)
            for d in (192, 128))
    jax.eval_shape(lambda a, b: attn.dot_product_attention(
        a, a, b, causal=True, impl="pallas"), q, v)
    line = capsys.readouterr().err.strip().splitlines()[-1]
    assert " tiles=136/256 masked=16 steps=72/128 fetches=70 bwd=fused " \
        in line


# cell -> the backward its attention shapes take (16384 keys of 128 + 128
# are past what the fused kernel keeps under a KV head)
CELLS = {"gpt2s-1chip-b16": "fused", "ling3f-1chip-ep64-s8k": "fused",
         "lagunas-1chip-ep32-w512": "fused", "ouro26b-1chip-ut4-s4k": "fused",
         "solar2-1chip-ep40-tp8": "fused", "lfm2moe-1chip-ep4-s8k": "fused",
         "mellum2-1chip-ep4-s16k": "split"}


@pytest.mark.parametrize("cell,form", CELLS.items(), ids=list(CELLS))
def test_every_benchmark_cells_attention_resolves_to_its_shapes_backward(
        monkeypatch, capsys, cell, form):
    """A benchmark cell's model traced at the cell's own batch and length
    where the dispatch sees a TPU (`gpt2s-dp4-b64` is the first cell's model
    at the same 16 sequences a chip): every attention shape it holds
    prints ONE `[attention] impl=pallas` line, however many layers call it,
    and each ends `bwd=fused resident=...MB`: the mechanism engages in
    every cell at 8192 keys or fewer, by the function the kernel's backward
    itself asks; the one cell at 16384 keys ends `bwd=split` on both its
    kinds, the band's and the full layer's, with what the fused kernel WOULD
    keep past the budget."""
    import json
    import os

    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.ops import attention as attn
    from pytorch_distributed_train_tpu.ops import flash_attention as fa

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "workloads", f"{cell}.json"),
              encoding="utf-8") as f:
        workload = json.load(f)
    with open(os.path.join(bench, "configs", f"{workload['config']}.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    cfg = get_preset(config["preset"])
    cfg.apply_overrides(list(config["overrides"])
                        + list(workload["overrides"]))
    monkeypatch.setattr(attn, "_on_tpu", lambda: True)
    attn._resolutions_logged.clear()
    model = build_model(cfg.model, cfg.precision)
    ids = jnp.zeros((cfg.data.batch_size, cfg.data.seq_len), jnp.int32)
    capsys.readouterr()
    jax.eval_shape(lambda rng: model.init({"params": rng}, ids, train=False),
                   jax.random.PRNGKey(0))
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("[attention] ")]
    assert lines and all("impl=pallas" in ln for ln in lines), lines
    assert len(set(lines)) == len(lines), lines  # once a shape
    kinds = len(set(cfg.model.layer_kinds) & {"gqa_full", "gqa_window",
                                              "mla"}) \
        if getattr(cfg.model, "layer_kinds", None) else 1
    assert len(lines) == kinds, lines
    for ln in lines:
        taken, resident = ln.split()[-2:]
        assert taken == "bwd=" + form, ln
        assert resident.startswith("resident=") and resident.endswith("MB")
        assert (float(resident[9:-2]) * 1e6 <= fa.FUSED_RESIDENT_BYTES) \
            == (form == "fused"), ln


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
