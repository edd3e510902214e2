"""Pallas TPU flash attention: fused online-softmax attention, fwd + bwd.

The TPU counterpart of the reference stack's fused attention kernels
(torch SDPA/cuDNN flash path — SURVEY C23): never materialises the (S, S)
score matrix in HBM. Forward keeps per-row running max/sum accumulators in
VMEM and streams KV blocks through the MXU (the flash-attention-2
formulation); backward recomputes P per block from the saved logsumexp and
accumulates dQ / dK / dV in two kernels.

Layout: inputs (B, S, H, D) are reshaped to (B·H, S, D); the kernel grid is
(B·H, S/block_q) with an inner arbitrary-order sweep over S/block_k. D must
be 64/128/256 (lane-aligned); S must divide by the block sizes. Softmax math
is fp32 regardless of input dtype (matches ops.attention policy).

GQA is native (r4): K/V stay at Hkv heads in HBM; the batch-major head
order makes q row b's KV row exactly b // rep (rep = H/Hkv), so sharing is
a BlockSpec index_map, not a materialised repeat — K/V read bandwidth drops
by rep. The dK/dV backward adds a rep grid axis that revisits each KV tile
once per query head in its group (first visit zeroes the accumulators,
last writes out).

Causal masking skips whole KV blocks above the diagonal (no wasted MXU work)
and applies an iota mask only on diagonal blocks. Sliding-window attention
(``window > 0``) additionally skips KV blocks entirely below the band, so
compute scales O(S·window) like the chunked XLA path.

Two entry points:
- :func:`flash_attention` — full self-attention, positions implied by the
  block grid (the single-device training path).
- :func:`flash_attention_chunk` — one Q block against one K/V chunk with
  EXPLICIT global position vectors, returning chunk-normalized output plus
  the logsumexp. This is the ring-attention inner kernel (SURVEY §5.7):
  the ring rotates K/V chunks (and their position vectors) around the
  'context' axis and merges chunk results with the flash rule, so the mask
  depends on traced positions, not grid indices. Its custom VJP folds the
  incoming lse cotangent into the flash2 ``delta`` term
  (ds = p∘(dp − (delta − dlse))), so the same backward kernels serve both
  entry points.

Enable/disable: dispatched from ops.attention.dot_product_attention; tests
run interpret=True on CPU against the XLA reference implementation
(SURVEY §5.2 "Pallas kernels → interpret=True mode vs XLA reference").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Tuned on TPU v5e (S=2048, D=128, bf16): large tiles amortize per-program
# overhead — 128x128 ran ~3.5x slower than 512x1024. VMEM check: the f32
# score tile is block_q x block_k x 4B = 2 MB, well inside the ~16 MB budget
# with q/k/v/acc blocks.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def supported(q, k, v, *, causal: bool, mask, window: int = 0) -> bool:
    # window composes with any supported shape (masking + band block skip);
    # it is accepted for API symmetry with the other backends.
    del window
    if mask is not None:
        return False
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if Sq != Sk:  # self-attention only (no KV-cache decode shapes)
        return False
    if D not in (64, 128, 256):
        return False
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv != H and (Hkv == 0 or H % Hkv != 0):
        return False  # invalid GQA ratio — let the XLA path raise clearly
    bq = min(DEFAULT_BLOCK_Q, Sq)
    bk = min(DEFAULT_BLOCK_K, Sk)
    return Sq % bq == 0 and Sk % bk == 0 and bq % 8 == 0 and bk % 128 == 0


def chunk_supported(q, k, v) -> bool:
    """Shape gate for :func:`flash_attention_chunk` (ring inner kernel):
    GQA-or-MHA heads (Hkv divides H — native in-kernel sharing, r4),
    lane-aligned D, block-divisible LOCAL seq lens (Sq is the device's Q
    shard, Sk the rotating chunk — they may differ)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    if k.shape != v.shape:
        return False
    if Hkv != H and (Hkv == 0 or H % Hkv != 0):
        return False
    if D not in (64, 128, 256):
        return False
    bq = min(DEFAULT_BLOCK_Q, Sq)
    bk = min(DEFAULT_BLOCK_K, Sk)
    return Sq % bq == 0 and Sk % bk == 0 and bq % 8 == 0 and bk % 128 == 0


# ------------------------------------------------------------- mask helpers
#
# Shared by all kernels. Positions: iota-from-grid for the full-seq entry,
# explicit (S, 1) i32 refs for the ring-chunk entry (traced, device-local).

def _block_keep(q_start, k_start, qpos_ref, kpos_ref, block_q, block_k,
                causal, window):
    """(block_q, block_k) keep-mask, or None when nothing masks."""
    if not causal and not window:
        return None
    if qpos_ref is not None:
        rows = qpos_ref[...].astype(jnp.int32)  # (block_q, 1)
        cols = kpos_ref[...].astype(jnp.int32).reshape(1, block_k)
    else:
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
    keep = rows >= cols if causal else None
    if window:
        band = (rows - cols) < window
        keep = band if keep is None else jnp.logical_and(keep, band)
    return keep


def _block_needed(q_start, k_start, qpos_ref, kpos_ref, block_q, block_k,
                  causal, window):
    """Scalar predicate: does this (Q block, KV block) pair intersect the
    causal triangle ∩ window band at all? None → always needed."""
    if not causal and not window:
        return None
    if qpos_ref is not None:
        qp = qpos_ref[...]
        kp = kpos_ref[...]
        q_min, q_max = jnp.min(qp), jnp.max(qp)
        k_min, k_max = jnp.min(kp), jnp.max(kp)
    else:
        q_min, q_max = q_start, q_start + block_q - 1
        k_min, k_max = k_start, k_start + block_k - 1
    needed = q_max >= k_min if causal else None
    if window:
        in_band = k_max > q_min - window
        needed = in_band if needed is None else jnp.logical_and(needed,
                                                                in_band)
    return needed


def profitable(q) -> bool:
    # Below ~1k tokens XLA's fused attention is already fine; flash pays off
    # when the score matrix stops fitting in VMEM.
    return q.shape[1] >= 1024


# ================================================================= forward

def _fwd_kernel(*refs, block_q, block_k, causal, scale, window, has_pos):
    """Grid (BH, nq, nk): one (block_q, D) output tile, sweeping KV blocks."""
    if has_pos:
        (q_ref, k_ref, v_ref, qpos_ref, kpos_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        qpos_ref = kpos_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0].astype(jnp.float32)  # (block_q, D)
        kb = k_ref[0].astype(jnp.float32)  # (block_k, D)
        vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)

        keep = _block_keep(q_start, k_start, qpos_ref, kpos_ref,
                           block_q, block_k, causal, window)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Rows with EVERY key masked so far (possible for ring chunks and
        # window bands): m_new == NEG_INF, and exp(s - m_new) would be
        # exp(0)=1 for the masked entries. Subtract 0 instead so p stays 0.
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)  # (block_q, block_k)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    needed = _block_needed(q_start, k_start, qpos_ref, kpos_ref,
                           block_q, block_k, causal, window)
    if needed is None:
        _body()
    else:
        pl.when(needed)(_body)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l_safe)


def _pos_specs(block_q, block_k):
    """BlockSpecs for the (S, 1) / (Sk, 1) i32 position inputs (shared
    across the BH grid axis)."""
    return [
        pl.BlockSpec((block_q, 1), lambda b, i, j: (i, 0)),
        pl.BlockSpec((block_k, 1), lambda b, i, j: (j, 0)),
    ]


def _fwd(q3, k3, v3, q_pos=None, kv_pos=None, *, causal, scale,
         block_q, block_k, window, interpret, out_dtype=None):
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    # GQA without HBM expansion (ROADMAP kernel follow-up): q3 is flattened
    # batch-major with heads in order, so q row b = (batch·Hkv + kvh)·rep + r
    # and its KV row is simply b // rep — an index_map, not a materialized
    # repeat. rep == 1 is the MHA/pre-expanded case (identity map).
    rep = BH // k3.shape[0]
    nq, nk = Sq // block_q, Sk // block_k
    grid = (BH, nq, nk)
    has_pos = q_pos is not None
    out_shape = [
        jax.ShapeDtypeStruct(q3.shape, out_dtype or q3.dtype),  # O
        jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),  # LSE (trailing 1: TPU block-shape alignment)
    ]
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k,
        causal=causal, scale=scale, window=window, has_pos=has_pos,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // rep, j, 0)),
    ]
    args = [q3, k3, v3]
    if has_pos:
        in_specs += _pos_specs(block_q, block_k)
        args += [q_pos, kv_pos]
    return pl.pallas_call(
        kernel,
        grid=grid,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


# ================================================================ backward
#
# flash2 backward: with P = exp(S - lse) and delta_i = rowsum(dO_i * O_i):
#   dV_j = sum_i P_ij^T dO_i
#   dP_ij = dO_i V_j^T
#   dS_ij = P_ij * (dP_ij - delta_i)
#   dQ_i = scale * sum_j dS_ij K_j
#   dK_j = scale * sum_i dS_ij^T Q_i

def _bwd_dq_kernel(*refs, block_q, block_k, causal, scale, window, has_pos):
    if has_pos:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qpos_ref, kpos_ref, dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
        qpos_ref = kpos_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # (block_q, 1)
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        keep = _block_keep(q_start, k_start, qpos_ref, kpos_ref,
                           block_q, block_k, causal, window)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        # Fully-masked rows carry lse == NEG_INF; exp(s - lse) would be
        # exp(0)=1 there — subtract 0 instead so p stays 0.
        lse_safe = jnp.where(lse <= NEG_INF / 2, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    needed = _block_needed(q_start, k_start, qpos_ref, kpos_ref,
                           block_q, block_k, causal, window)
    if needed is None:
        _body()
    else:
        pl.when(needed)(_body)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, block_q, block_k, causal, scale, window, has_pos):
    """Grid (B·Hkv, nk, rep, nq): one (block_k, D) dK/dV tile. The rep axis
    revisits the SAME KV tile for each of the rep query heads sharing it
    (GQA) — first visit (r==0, qi==0) zeroes the accumulators, every visit
    adds, the last (r==rep-1, qi==nq-1) writes out. rep==1 is MHA."""
    if has_pos:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qpos_ref, kpos_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qpos_ref = kpos_ref = None
    ki = pl.program_id(1)
    r = pl.program_id(2)
    qi = pl.program_id(3)
    rep = pl.num_programs(2)
    nq = pl.num_programs(3)

    @pl.when((qi == 0) & (r == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # (block_q, 1)
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        keep = _block_keep(q_start, k_start, qpos_ref, kpos_ref,
                           block_q, block_k, causal, window)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        lse_safe = jnp.where(lse <= NEG_INF / 2, 0.0, lse)
        p = jnp.exp(s - lse_safe)  # (block_q, block_k)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_k, D)
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_k, D)

    needed = _block_needed(q_start, k_start, qpos_ref, kpos_ref,
                           block_q, block_k, causal, window)
    if needed is None:
        _body()
    else:
        pl.when(needed)(_body)

    @pl.when((qi == nq - 1) & (r == rep - 1))
    def _fin():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, o3, lse, do3, q_pos=None, kv_pos=None, *, causal,
         scale, block_q, block_k, window, interpret, dlse=None):
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    rep = BH // k3.shape[0]  # GQA group size (see _fwd); 1 = MHA
    nq, nk = Sq // block_q, Sk // block_k
    has_pos = q_pos is not None
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[..., None]
    if dlse is not None:
        # Chunk entry: the lse output has its own cotangent. With
        # lse = logsumexp(s), d lse/d s = p, so ds gains +p·dlse — which
        # folds into the flash2 formula as delta' = delta − dlse.
        delta = delta - dlse

    dq_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    dq_args = [q3, k3, v3, do3, lse, delta]
    if has_pos:
        dq_in_specs += _pos_specs(block_q, block_k)
        dq_args += [q_pos, kv_pos]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, window=window,
                          has_pos=has_pos),
        grid=(BH, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*dq_args)

    # dK/dV grid (B·Hkv, nk, rep, nq): q-side rows for KV row b are
    # b·rep + r — the inverse of the forward's b // rep map.
    dkv_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, j, r, i: (b * rep + r, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, j, r, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, j, r, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, D), lambda b, j, r, i: (b * rep + r, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, r, i: (b * rep + r, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, r, i: (b * rep + r, i, 0)),
    ]
    dkv_args = [q3, k3, v3, do3, lse, delta]
    if has_pos:
        dkv_in_specs += [
            pl.BlockSpec((block_q, 1), lambda b, j, r, i: (i, 0)),
            pl.BlockSpec((block_k, 1), lambda b, j, r, i: (j, 0)),
        ]
        dkv_args += [q_pos, kv_pos]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, window=window,
                          has_pos=has_pos),
        grid=(BH // rep, nk, rep, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, r, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, r, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*dkv_args)
    return dq, dk, dv


# ============================================================== public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, scale, block_sizes, interpret, window):
    o, _ = _fwd(q3, k3, v3, causal=causal, scale=scale,
                block_q=block_sizes[0], block_k=block_sizes[1],
                window=window, interpret=interpret)
    return o


def _flash_fwd(q3, k3, v3, causal, scale, block_sizes, interpret, window):
    o, lse = _fwd(q3, k3, v3, causal=causal, scale=scale,
                  block_q=block_sizes[0], block_k=block_sizes[1],
                  window=window, interpret=interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, scale, block_sizes, interpret, window, res, do3):
    q3, k3, v3, o3, lse = res
    dq, dk, dv = _bwd(q3, k3, v3, o3, lse, do3, causal=causal, scale=scale,
                      block_q=block_sizes[0], block_k=block_sizes[1],
                      window=window, interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """(B, S, H, D) attention via the Pallas kernel. GQA (Hkv < H,
    H % Hkv == 0) is NATIVE: K/V stay at Hkv heads in HBM and the kernel's
    BlockSpec index_map (q row b → KV row b // rep) shares each KV tile
    across its query group — no expanded copy is ever materialised
    (forward reads H/Hkv x less K/V bandwidth than an expand-first
    design). ``window`` > 0 restricts each query to its trailing
    ``window`` keys (requires causal — enforced upstream)."""
    if k.shape != v.shape:
        raise ValueError(f"k/v shapes differ: {k.shape} vs {v.shape}")
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H and (Hkv == 0 or H % Hkv != 0):
        raise ValueError(
            f"invalid GQA ratio: {H} query heads over {Hkv} KV heads")
    bq = min(block_q, S)
    bk = min(block_k, S)
    scale = float(1.0 / (D ** 0.5))

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], S, D)

    o3 = _flash(to3(q), to3(k), to3(v), causal, scale, (bq, bk), interpret,
                int(window))
    return o3.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ----------------------------------------------------- ring-chunk entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_chunk(q3, k3, v3, qp, kp, causal, scale, block_sizes, interpret,
                 window):
    o, lse = _fwd(q3, k3, v3, qp, kp, causal=causal, scale=scale,
                  block_q=block_sizes[0], block_k=block_sizes[1],
                  window=window, interpret=interpret, out_dtype=jnp.float32)
    return o, lse


def _flash_chunk_fwd(q3, k3, v3, qp, kp, causal, scale, block_sizes,
                     interpret, window):
    o, lse = _flash_chunk(q3, k3, v3, qp, kp, causal, scale, block_sizes,
                          interpret, window)
    return (o, lse), (q3, k3, v3, qp, kp, o, lse)


def _flash_chunk_bwd(causal, scale, block_sizes, interpret, window, res, ct):
    q3, k3, v3, qp, kp, o3, lse = res
    do3, dlse = ct
    dq, dk, dv = _bwd(q3, k3, v3, o3, lse, do3.astype(jnp.float32), qp, kp,
                      causal=causal, scale=scale,
                      block_q=block_sizes[0], block_k=block_sizes[1],
                      window=window, interpret=interpret, dlse=dlse)
    zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # noqa: E731
    return dq, dk, dv, zero(qp), zero(kp)


_flash_chunk.defvjp(_flash_chunk_fwd, _flash_chunk_bwd)


def flash_attention_chunk(q, k, v, q_pos, kv_pos, *, causal: bool,
                          window: int = 0,
                          block_q: int = DEFAULT_BLOCK_Q,
                          block_k: int = DEFAULT_BLOCK_K,
                          interpret: bool = False):
    """One Q shard against ONE K/V chunk with explicit global positions —
    the ring-attention inner step (ops/ring_attention.py).

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D) — GQA taken UNEXPANDED (the
    in-kernel b // rep sharing, r4); q_pos: (Sq,) i32;
    kv_pos: (Sk,) i32 (traced — they rotate with the chunk).
    Returns (o, lse): o (B, Sq, H, D) fp32 normalized WITHIN the chunk,
    lse (B, H, Sq) fp32, NEG_INF on fully-masked rows — the contract
    ring_attention's merge rule expects. Differentiable in q/k/v including
    through lse (the merge weights), via the folded-delta custom VJP.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    scale = float(1.0 / (D ** 0.5))
    qp = q_pos.astype(jnp.int32).reshape(Sq, 1)
    kp = kv_pos.astype(jnp.int32).reshape(Sk, 1)

    def to3(x):  # per-tensor head count: k/v stay at Hkv rows (GQA)
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2],
                                               x.shape[1], D)

    o3, lse = _flash_chunk(to3(q), to3(k), to3(v), qp, kp, causal, scale,
                           (bq, bk), interpret, int(window))
    o = o3.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return o, lse.reshape(B, H, Sq)
