"""Pallas TPU LM head + next-token cross-entropy: the loss's two passes over
the logits ride the head's products.

Left to XLA, ``logits = x · W^T`` followed by softmax cross-entropy reads
the float32 logits three times besides writing them (PERF.md section 5):
the product's epilogue takes the row maximum, a loop fusion reads them
for ``sum(exp(l - max))``, and in the backward pass a second loop fusion
reads them again to write ``dl = (softmax - onehot) * weight / count`` in
bfloat16, which the two backward products then read. Here the forward
kernel keeps the running maximum and rescaled sum of ``exp`` across the
vocabulary tiles of the product it computes, so the row's log-sum-exp and
the label's logit leave with the logits; the backward kernel forms ``dl``
tile by tile from the logits it reads ONCE, multiplies it into the
weights' gradient (accumulator in VMEM) and writes the bfloat16 tile for
the other product, ``dX = dl · W``, which stays XLA's. The logits are
kept, in float32: their write hides under the MXU and a recompute (a
fourth product) would not.

Layout. The logits live TRANSPOSED, ``(V, N)``: vocabulary on sublanes and
the N = B·S rows of ``x`` on lanes (``L^T = W x^T``, W the ``(V, C)``
table as the tied embedding stores it). Every per-row statistic (running
maximum and sum, log-sum-exp, label, the label's logit, the incoming
cotangent) is then a lane-dense ``(1, tile_n)`` row that broadcasts along
sublanes, and the reductions run down sublanes, as in
ops/flash_attention.py. Nobody else reads the array, so its layout is the
kernels' own.

Precision is the logits path's, rounding point for rounding point:
operands in the compute dtype with float32 accumulation, float32 logits
and log-sum-exp, the row's scale ``weight / count`` (the cotangent of the
per-token loss) applied in float32 BEFORE ``dl`` is rounded to the
operands' dtype (where XLA rounds it today, on its way into the MXU),
float32 accumulation in both backward products. ``exp(l - lse)`` stands
for ``exp(l - max) / sum``: the same number to float32 rounding.

Vocabulary 50304 = 2^7 · 3 · 131 has no power-of-two tile above 128: the
last vocabulary tile is ragged. The forward kernel masks its rows >= V
to -inf before the running maximum; the backward kernel need not (row r
of ``dW`` and of ``dl^T`` depends on row r of the logits alone, and rows
>= V of an edge block are never written back), and the table is not
padded. Rows of ``x`` must tile evenly: the caller passes ALL B·S rows
and gives each sequence's last position weight 0 (the shift).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_train_tpu.ops.flash_attention import (
    _NN,
    _NT,
    _TN,
    NEG_INF,
    _dot,
)


class Tiles(NamedTuple):
    n: int    # rows of x (lanes of the logits) a grid step
    v: int    # vocabulary rows (sublanes of the logits) a grid step
    sub: int  # vocabulary rows a pass inside a step: one product, one
    #           epilogue, so the next product can run under it


def tile_sizes(rows: int, vocab: int, *, tile_n: int | None = None,
               tile_v: int | None = None, sub_v: int | None = None) -> Tiles:
    """The one tile rule, from what a call can see. Explicit sizes (tests,
    tuning) override the rule's."""
    # Read on a v5e at (16384, 50304, 768) bfloat16 (PERF.md section 6, PR
    # 29): the step is bound by the MXU and the logits' own traffic, and
    # tiles past 1024 x 1024 only trim the operands' re-reads: 1024 x 2048
    # ran the whole step 0.25 % faster, 512 x 4096 and 2048 x 1024 no
    # faster than 1024 x 1024; rows of 512 re-read the table twice as often
    # (the forward alone 8.83 ms against 7.49).
    tn = tile_n or next(t for t in (1024, 512, 256, 128) if rows % t == 0)
    tv = tile_v or min(2048, -(-vocab // 128) * 128)
    sub = sub_v or next(t for t in (256, 128) if tv % t == 0)
    if rows % tn or tn % 128 or tv % sub or sub % 8:
        raise ValueError(
            f"lm head tiles ({tn}, {tv}, {sub}) do not fit rows={rows}: "
            "tile_n must be a multiple of 128 that divides the rows, "
            "sub_v a multiple of 8 that divides tile_v")
    return Tiles(tn, tv, sub)


# A step holds a (tile_v, tile_n) float32 logits tile twice (double
# buffering), its bfloat16 twin, the operands' tiles and an accumulator:
# 33 MiB at 1024 x 2048, past the 16 MiB a kernel gets unasked. A v5e's
# core has 128 MiB.
_VMEM_LIMIT = 64 << 20


def _vmem_limit(tiles: Tiles, width: int, itemsize: int) -> int:
    """The limit a kernel asks for: 64 MiB where the backward step's blocks
    (the larger of the two kernels') leave it a fifth of room, as at width
    768 (39 MiB), else their size and a quarter more: at width 2048 the
    accumulator and the gradient's tile alone are 32 MiB, the step 64 MiB,
    and the compiler refused it by 16 KiB."""
    tn, tv, _ = tiles
    need = (2 * tv * tn * (4 + itemsize)      # logits in, dl out, x2 buffers
            + 2 * (tn + tv) * width * itemsize  # x in, dW out, x2 buffers
            + 4 * tv * width)                   # the float32 accumulator
    return _VMEM_LIMIT if 5 * need <= 4 * _VMEM_LIMIT else need + need // 4


def _row_ids(shape, first):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) + first


# ================================================================= forward

def _fwd_kernel(x_ref, w_ref, lab_ref, lt_ref, lse_ref, pick_ref,
                m_ref, l_ref, p_ref, *, vocab, sub):
    """Grid (n tiles, v tiles), vocabulary innermost: one (tile_v, tile_n)
    tile of the transposed logits a step, the running statistics of its
    tile_n rows in VMEM across the vocabulary."""
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1
    tv = w_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        p_ref[...] = jnp.zeros_like(p_ref)

    def update(ragged):
        x = x_ref[...]
        m, l, pick = m_ref[...], l_ref[...], p_ref[...]
        # the label's row inside this tile (anywhere else: no match)
        want = lab_ref[...] - j * tv
        for c in range(0, tv, sub):
            s = _dot(w_ref[c:c + sub, :], x, _NT)  # (sub, tile_n)
            lt_ref[c:c + sub, :] = s
            rows = _row_ids(s.shape, c)
            pick = pick + jnp.sum(jnp.where(rows == want, s, 0.0), axis=0,
                                  keepdims=True)
            if ragged:  # rows past the vocabulary hold whatever was there
                s = jnp.where(rows < vocab - j * tv, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new), axis=0,
                                                 keepdims=True)
            m = m_new
        m_ref[...], l_ref[...], p_ref[...] = m, l, pick

    if vocab % tv:
        pl.when(j < last)(functools.partial(update, False))
        pl.when(j == last)(functools.partial(update, True))
    else:
        update(False)

    @pl.when(j == last)
    def _finish():
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])
        pick_ref[...] = p_ref[...]


def _fwd(x, w, labels, tiles: Tiles, interpret: bool):
    N, C = x.shape
    V = w.shape[0]
    tn, tv, sub = tiles
    row = pl.BlockSpec((1, tn), lambda i, j: (0, i))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, vocab=V, sub=sub),
        grid=(N // tn, pl.cdiv(V, tv)),
        in_specs=[
            pl.BlockSpec((tn, C), lambda i, j: (i, 0)),
            pl.BlockSpec((tv, C), lambda i, j: (j, 0)),
            row,
        ],
        out_specs=[pl.BlockSpec((tv, tn), lambda i, j: (j, i)), row, row],
        out_shape=[
            jax.ShapeDtypeStruct((V, N), jnp.float32),  # logits, transposed
            jax.ShapeDtypeStruct((1, N), jnp.float32),  # log-sum-exp
            jax.ShapeDtypeStruct((1, N), jnp.float32),  # the label's logit
        ],
        scratch_shapes=[pltpu.VMEM((1, tn), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="lm_head_fwd",
        interpret=interpret,
    )(x, w, labels)


# ================================================================ backward

def _bwd_kernel(lt_ref, lse_ref, lab_ref, g_ref, x_ref, dlt_ref, dw_ref,
                acc_ref, *, sub):
    """Grid (v tiles, n tiles), rows innermost: one (tile_v, C) tile of the
    weights' gradient, accumulated in VMEM over the rows; each step reads
    its logits tile once and writes the same tile of ``dl^T``."""
    j, i = pl.program_id(0), pl.program_id(1)
    tv = lt_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    lse, g = lse_ref[...], g_ref[...]
    want = lab_ref[...] - j * tv
    for c in range(0, tv, sub):
        p = jnp.exp(lt_ref[c:c + sub, :] - lse)
        d = jnp.where(_row_ids(p.shape, c) == want, p - 1.0, p) * g
        d = d.astype(dlt_ref.dtype)  # today's rounding point
        dlt_ref[c:c + sub, :] = d
        acc_ref[c:c + sub, :] += _dot(d, x, _NN)  # (sub, C)

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _bwd(lt, lse, labels, g, x, w_dtype, tiles: Tiles, interpret: bool):
    V, N = lt.shape
    C = x.shape[1]
    tn, tv, sub = tiles
    row = pl.BlockSpec((1, tn), lambda j, i: (0, i))
    tile = pl.BlockSpec((tv, tn), lambda j, i: (j, i))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub),
        grid=(pl.cdiv(V, tv), N // tn),
        in_specs=[tile, row, row, row,
                  pl.BlockSpec((tn, C), lambda j, i: (i, 0))],
        out_specs=[tile, pl.BlockSpec((tv, C), lambda j, i: (j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((V, N), x.dtype),   # dl, transposed
            jax.ShapeDtypeStruct((V, C), w_dtype),   # the weights' gradient
        ],
        scratch_shapes=[pltpu.VMEM((tv, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tiles, C, x.dtype.itemsize)),
        name="lm_head_bwd",
        interpret=interpret,
    )(lt, lse, labels, g, x)


# ============================================================== entry point

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _token_xent(x, w, labels, tiles, interpret):
    return _token_xent_fwd(x, w, labels, tiles, interpret)[0]


def _token_xent_fwd(x, w, labels, tiles, interpret):
    labels = labels[None]
    lt, lse, pick = _fwd(x, w, labels, tiles, interpret)
    return (lse - pick)[0], (x, w, labels, lt, lse)


def _token_xent_bwd(tiles, interpret, res, g):
    x, w, labels, lt, lse = res
    dlt, dw = _bwd(lt, lse, labels, g[None].astype(jnp.float32), x, w.dtype,
                   tiles, interpret)
    dx = _dot(dlt, w, _TN).astype(x.dtype)  # dl · W, XLA's
    return dx, dw, np.zeros(labels.shape[1:], jax.dtypes.float0)


_token_xent.defvjp(_token_xent_fwd, _token_xent_bwd)


def token_xent(x, w, labels, *, transposed_w: bool = True,
               interpret: bool = False, tile_n: int | None = None,
               tile_v: int | None = None, sub_v: int | None = None):
    """Per-row softmax cross-entropy of ``x · W^T`` against ``labels``,
    float32 ``(N,)``, differentiable in ``x`` and ``w``.

    x: (N, C) final hidden states; w: the (V, C) table a tied head reads
    (``transposed_w``), or a plain (C, V) kernel; labels: (N,) int32. The
    caller weights and averages the rows: a row's cotangent is its scale
    ``weight / count``, which the backward kernel applies in float32.
    """
    if not transposed_w:
        w = w.T  # one pass over the kernel, and back over its gradient
    tiles = tile_sizes(x.shape[0], w.shape[0], tile_n=tile_n, tile_v=tile_v,
                       sub_v=sub_v)
    return _token_xent(x, w, labels.astype(jnp.int32), tiles, interpret)
