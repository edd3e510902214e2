"""Pallas TPU flash attention: fused online-softmax attention, fwd + bwd.

The TPU counterpart of the reference stack's fused attention kernels
(torch SDPA/cuDNN flash path — SURVEY C23): never materialises the (S, S)
score matrix in HBM. Forward streams KV through the MXU under a running
max/sum (the flash-attention-2 formulation); backward recomputes P per
tile from the saved logsumexp and produces dQ, dK and dV in ONE kernel:
a score tile's S^T, P^T and dS^T are built once and the three output
products run from them, five products a tile (a dQ kernel beside a dK/dV
kernel runs seven: both rebuild S^T and dP^T). The two-kernel backward
lives on for calls past the fused kernel's VMEM budget alone.

Layout. Inputs (B, S, H, D) are reshaped to (B·H, S, D). Inside a kernel
the scores live TRANSPOSED, keys on sublanes and queries on lanes
(S^T = K Q^T), so every per-query statistic — running max and sum, lse,
delta, the queries' positions — is a lane-dense (1, block_q) row that
broadcasts along sublanes, the softmax reductions run down sublanes (VPU
work, no cross-lane shuffles), dV = P^T dO and dK = dS^T Q are plain
products, and only the small (D, block_q) results O^T and dQ^T are
transposed, once, on the way out. lse and delta are (B·H, 1, S) in HBM: a
row a head, not an (S, 1) column padded to 128 lanes. D must be
64/128/192/256 (V's may differ from Q's and K's); S must divide by 128.

Tiling is two-level (:func:`tile_sizes` is the one rule). The GRID stays
coarse, (B·H, S/block_q, S/major): a grid step has fixed costs. A step
holds ``major`` rows of K and V in VMEM; with one major block (S <= 2048)
the resident block's index does not depend on the other index, so it is
fetched once a head. The backward's grid is the forward's under a KV
head with the head's rep query heads innermost, (B·Hkv, S/block_q,
S/major, rep): the rep steps of a (Q tile, major block) pair share ONE
fetch of K and V (at nine query heads a KV head the forward's grid asks
for them nine times as often as the backward's), a Q tile's Q, dO and dQ
are blocks of all rep heads, fetched and written once a tile, and dK and
dV of the KV head's WHOLE sequence stay in float32 VMEM under every Q
tile and query head that reads the head, S x (D + Dv) x 4 bytes whatever
rep is (0.5 MB at GPT-2's shape, 10.5 MB at latent attention's 8192 x
(192 + 128)), beside the output blocks they are written to at the head's
last step: :func:`backward_plan` is the one rule, and the call asks the
compiler for the scoped VMEM it needs (the v5e has 128 MiB). The other
side resident, dQ under a dK/dV grid, would hold rep x S x D x 4: 37.7 MB
at nine heads a KV head. Past the budget (16384 keys at D 128, or more
than 16 query heads a KV head at 8192) a call keeps the two kernels, whose
dK/dV step holds ``major`` rows of Q, dO, lse and delta and owns one
(block_k, D) output tile.

What is skipped and what is masked, full-sequence entry (positions implied
by the grid). Which (block_q, block_k) score tiles a step enters follows
from one number, the offset between its first query and its first key,
and the grid can produce only a few such offsets: each gets its own
straight-line specialisation under a ``pl.when`` on the grid indices.
Tiles wholly above the causal diagonal or below the window band are not in
it at all; neighbouring tiles wholly inside are ONE wide segment with no
mask code; only the tiles that cross the diagonal or the band's edge build
a mask (lane - sublane iota against one scalar). :func:`tile_plan` counts
the three kinds: causal S 1024 at 512 x 512 enters 3 of 4 tiles a head and
masks 2. Sliding-window attention (``window`` > 0) therefore scales
O(S·window) like the chunked XLA path. A step with one major block writes
its output itself: no running state, no scratch, no init/finalize — on a
v5e that state cost a third of the old forward (PERF.md, PR 25). Several
major blocks (S > 2048; S > 1024 at D 192, where 512 KiB of K are 1365
rows) keep the running max/sum/accumulator in VMEM across them. A step
whose major block lies wholly above the diagonal or below the window's
band runs nothing AND fetches nothing: K's and V's index maps give it the
nearest block its Q tile does enter (:func:`block_map`, one rule for the
forward, the fused backward and both kernels of the split one, held
against the dispatch's own ranges as it is built), consecutive steps then
share an index and Pallas skips the copy. :class:`FetchPlan` counts it:
of a head's 128 grid steps at latent attention's (8192, D 192) 72 enter a
tile and 70 blocks are fetched (2.9 GB of K and V a call where a block a
step is 5.4); 64 / 40 / 36 at (8192, D 128), 64 / 19 / 4 under a window of
512. The empty steps stay in the grid and keep a step's fixed cost.

Precision: scores, softmax statistics, ``exp``, lse, delta and every
accumulator are fp32 regardless of input dtype (matches ops.attention
policy). Products take stored operands (q, k, v, dO) in their stored
dtype with fp32 accumulation — a bf16 x bf16 product is exact in fp32 —
and computed ones (P, dS) as fp32, which Mosaic's default-precision dot
feeds to the MXU in one bf16 pass on a v5e: casting them first changed
neither the result nor the time (PERF.md, PR 25).

GQA is native (r4): K/V stay at Hkv heads in HBM; the batch-major head
order makes q row b's KV row exactly b // rep (rep = H/Hkv), so sharing is
a BlockSpec index_map, not a materialised repeat — K/V read bandwidth drops
by rep. The backward's rep grid axis, innermost, walks the query heads
of a KV head under one fetched K and V block and the head's resident dK
and dV (the head's first step zeroes them, its last writes out); the
two-kernel dK/dV revisits each KV tile once per query head.

Two entry points:
- :func:`flash_attention` — full self-attention, positions implied by the
  block grid (the single-device training path).
- :func:`flash_attention_chunk` — one Q block against one K/V chunk with
  EXPLICIT global position vectors, returning chunk-normalized output plus
  the logsumexp. This is the ring-attention inner kernel (SURVEY §5.7):
  the ring rotates K/V chunks (and their position vectors) around the
  'context' axis and merges chunk results with the flash rule, so the mask
  depends on traced positions, not grid indices: there is no static
  diagonal, so every block_k chunk of the resident block is entered under
  a predicate on its positions' min/max and masked from the positions.
  Same algorithm, different bound. Its custom VJP folds the incoming lse
  cotangent into the flash2 ``delta`` term (ds = p∘(dp − (delta − dlse))),
  so the same backward serves both entry points, fused or not.

Enable/disable: dispatched from ops.attention.dot_product_attention; tests
run interpret=True on CPU against the XLA reference implementation
(SURVEY §5.2 "Pallas kernels → interpret=True mode vs XLA reference").
"""

from __future__ import annotations

import functools
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_train_tpu.ops.attention import FLASH_RESIDUALS_NAME

NEG_INF = -1e30

# Score tiles of 512 x 512 where S divides (else 256, else 128), inside
# grid steps that hold up to 2048 rows (and at most 512 KiB) of the
# streamed side. Read on a v5e at (16, 1024, 12, 64) bf16 causal (PERF.md,
# PR 25): the kernels are bound by the MXU and by what a grid step costs,
# not by the VPU (dropping mask, exp, max and sum together moved the
# forward 12 %), so tiles are as large as still leaves a diagonal to skip:
# 512 enters 3 of 4 tiles at S 1024 and ran 2.37 ms forward + backward a
# layer against 2.78 at 256 (10 of 16) and 2.67 with nothing skipped. At
# (8, 2048, 8, 128): 2.46 ms with one 2048-row major block, 3.09 with two
# of 1024. VMEM at D 128: K and V double-buffered 2 MiB, a (2048, 512)
# fp32 score segment 4 MiB, a few of them live: inside the scoped default.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
DEFAULT_BLOCK_K_MAJOR = 2048
_MAJOR_BYTES = 512 << 10

class Tiles(NamedTuple):
    block_q: int  # Q rows of a score tile (and of the fwd/bwd grid step)
    block_k: int  # KV columns of a score tile (and of a split dK/dV step)
    major_q: int  # Q rows resident in a split dK/dV grid step
    major_k: int  # KV rows resident in a forward / backward grid step


class TilePlan(NamedTuple):
    total: int     # score tiles in the S x S square of one head
    executed: int  # entered by the kernels
    masked: int    # of those, the ones that build a mask


def _fit(S: int, cap: int, unit: int) -> int:
    """Largest multiple of ``unit`` that divides S and is at most ``cap``."""
    best = unit
    for m in range(unit, min(S, cap) + 1, unit):
        if S % m == 0:
            best = m
    return best


def tile_sizes(Sq: int, Sk: int, D: int, itemsize: int, *,
               block_q: int | None = None, block_k: int | None = None,
               block_k_major: int | None = None) -> Tiles:
    """The one tile rule, from what a call can see. Explicit sizes (tests)
    override the rule's; every size must divide its sequence length."""
    def sub(S, want, default):
        if not want:
            want = next(t for t in (default, 256, 128) if S % t == 0)
        return min(want, S)

    bq = sub(Sq, block_q, DEFAULT_BLOCK_Q)
    bk = sub(Sk, block_k, DEFAULT_BLOCK_K)
    cap = block_k_major or min(DEFAULT_BLOCK_K_MAJOR,
                               _MAJOR_BYTES // (D * itemsize))
    if Sq % bq or Sk % bk or bq % 128 or bk % 128:
        raise ValueError(
            f"flash attention tiles ({bq}, {bk}) do not fit S=({Sq}, {Sk}): "
            "both must be multiples of 128 that divide the sequence")
    return Tiles(bq, bk, _fit(Sq, cap, bq), _fit(Sk, cap, bk))


def tile_plan(S: int, block_q: int, block_k: int, *, causal: bool,
              window: int = 0) -> TilePlan:
    """Score tiles of one head of the full-sequence entry: in the square,
    entered, and masked. The same set in every kernel (forward and
    backward sweep a Q tile's KV tiles, a split dK/dV a KV tile's Q tiles),
    the backward entering each ONCE; static per call
    site, so it costs a step nothing. With d = row - col: a tile is entered
    unless every d < 0 (causal) or every d >= window; it is masked when
    some d < 0 or some d >= window."""
    total = executed = masked = 0
    for q0 in range(0, S, block_q):
        for k0 in range(0, S, block_k):
            total += 1
            d_max = q0 + block_q - 1 - k0
            d_min = q0 - (k0 + block_k - 1)
            if (causal and d_max < 0) or (window and d_min >= window):
                continue
            executed += 1
            masked += bool((causal and d_min < 0)
                           or (window and d_max >= window))
    return TilePlan(total, executed, masked)


def call_plan(q, k, *, causal: bool, window: int = 0) -> TilePlan:
    """:func:`tile_plan` of a full-sequence call under the tile rule — what
    the dispatch's resolution line prints."""
    block_q, block_k, _, _ = tile_sizes(q.shape[1], k.shape[1], q.shape[3],
                                        q.dtype.itemsize)
    return tile_plan(q.shape[1], block_q, block_k, causal=causal,
                     window=window)


# Head dims the kernels have compiled and run at on a v5e. 192 is latent
# attention's Q and K (128 plain dims beside 64 rotated ones) beside V of
# 128: native, not zero-padded to 256 — at (2, 8192, 32, 192/128) forward
# and backward took 65.97 ms against 70.49 padded (my chip run, PR 26).
HEAD_DIMS = (64, 128, 192, 256)


def _tileable(q, k, v=None) -> bool:
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if D not in HEAD_DIMS or (v is not None and v.shape[3] not in HEAD_DIMS):
        return False
    if Hkv != H and (Hkv == 0 or H % Hkv != 0):
        return False  # invalid GQA ratio — let the XLA path raise clearly
    return Sq % 128 == 0 and Sk % 128 == 0


def supported(q, k, v, *, causal: bool, mask, window: int = 0) -> bool:
    # window composes with any supported shape (masking + band tile skip);
    # it is accepted for API symmetry with the other backends.
    del window
    if mask is not None:
        return False
    if q.shape[1] != k.shape[1]:  # self-attention only (no KV-cache decode)
        return False
    return _tileable(q, k, v)


def chunk_supported(q, k, v) -> bool:
    """Shape gate for :func:`flash_attention_chunk` (ring inner kernel):
    GQA-or-MHA heads (Hkv divides H — native in-kernel sharing, r4),
    lane-aligned D, tile-divisible LOCAL seq lens (Sq is the device's Q
    shard, Sk the rotating chunk — they may differ)."""
    return k.shape == v.shape and _tileable(q, k)


def profitable(q) -> bool:
    # Below ~1k tokens XLA's fused attention is already fine; flash pays off
    # when the score matrix stops fitting in VMEM.
    return q.shape[1] >= 1024


# ------------------------------------------------ score tiles and masks
#
# Shared by all kernels. A grid step meets ``major`` rows of its streamed
# side; which (block_q, block_k) score tiles of that meeting are entered,
# and which of them build a mask, follows from ONE number: the offset
# between the step's first query and first key. Positions: implied by the
# grid for the full-seq entry, explicit i32 refs for the ring-chunk entry
# (traced, device-local): queries a (1, Sq) row, keys an (Sk, 1) column.

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    """fp32-accumulated product; operands of two dtypes meet in the wider."""
    if a.dtype != b.dtype:
        t = jnp.promote_types(a.dtype, b.dtype)
        a, b = a.astype(t), b.astype(t)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _cdiv(a, b):
    return (a + b - 1) // b


def _clamp_ranges(lo, full_lo, full_hi, hi):
    a = min(max(full_lo, lo), hi)
    b = min(max(full_hi, a), hi)
    return lo, a, b, hi


def _kv_ranges(off, n, block_q, block_k, causal, window):
    """One Q tile against n KV tiles, ``off`` = its first row less their
    first column: (lo, a, b, hi) in tiles — [lo, a) cross the band's lower
    edge, [a, b) lie wholly inside, [b, hi) cross the diagonal; tiles
    outside [lo, hi) are never entered."""
    def pos(x):
        return max(x, 0)

    hi = min(n, _cdiv(pos(off + block_q), block_k)) if causal else n
    full_hi = pos(off + 1) // block_k if causal else n
    lo = pos(off - window + 1) // block_k if window else 0
    full_lo = _cdiv(pos(off + block_q - window), block_k) if window else 0
    return _clamp_ranges(lo, full_lo, full_hi, hi)


def _q_ranges(off, n, block_q, block_k, causal, window):
    """One KV tile against n Q tiles, ``off`` = its first column less
    their first row: [lo, a) cross the diagonal, [a, b) lie wholly inside,
    [b, hi) cross the band's lower edge."""
    def pos(x):
        return max(x, 0)

    lo = pos(off) // block_q if causal else 0
    full_lo = _cdiv(pos(off + block_k - 1), block_q) if causal else 0
    hi = min(n, _cdiv(pos(off + block_k - 1 + window), block_q)) \
        if window else n
    full_hi = pos(off + window) // block_q if window else n
    return _clamp_ranges(lo, full_lo, full_hi, hi)


def _segments(ranges, unit, mask_off):
    """(lo, a, b, hi) in tiles -> ((start, stop, mask), ...) along the
    streamed side: neighbouring tiles of one kind become ONE wide
    segment, so a grid step runs at most three straight-line pieces
    however fine the tiles. ``mask`` is None for a segment wholly inside,
    else the scalar that turns local row - local col into a keep-mask
    there (``mask_off(start)``)."""
    lo, a, b, hi = ranges
    return tuple(
        (x * unit, y * unit, mask_off(x * unit) if masked else None)
        for x, y, masked in ((lo, a, True), (a, b, False), (b, hi, True))
        if x < y)


def _kv_segments(off, major, block_q, block_k, causal, window):
    """What a Q tile enters of a resident K/V block, ``off`` = the tile's
    first row less the block's first column; empty: nothing."""
    return _segments(_kv_ranges(off, major // block_k, block_q, block_k,
                                causal, window), block_k, lambda c0: c0 - off)


def _q_segments(off, major, block_q, block_k, causal, window):
    """What a KV tile enters of a resident block of Q's side (the split
    dK/dV), ``off`` = the tile's first column less the block's first row."""
    return _segments(_q_ranges(off, major // block_q, block_q, block_k,
                               causal, window), block_q, lambda r0: off - r0)


def _static_dispatch(off, offsets, segments_of, update):
    """The full-seq entry: the step's offset is an expression of grid
    indices, and the grid can produce only ``offsets``. Neighbouring
    offsets with one segment list share a straight-line specialisation of
    ``update`` under one ``pl.when``; offsets with nothing to enter get
    none (with one major block every offset enters its diagonal). A
    single list for every offset needs no branch."""
    runs = []
    for o in sorted(set(offsets)):
        segs = segments_of(o)
        if runs and runs[-1][2] == segs:
            runs[-1][1] = o
        else:
            runs.append([o, o, segs])
    if len(runs) == 1:
        if runs[0][2]:
            update(runs[0][2])
        return
    for first, last, segs in runs:
        if segs:
            cond = off == first if first == last else \
                jnp.logical_and(off >= first, off <= last)
            pl.when(cond)(functools.partial(update, segs))


def _keep(diff, off, causal, window):
    """Keep-mask of a score tile from ``diff - off`` = row - col."""
    keep = diff >= off if causal else None
    if window:
        band = diff < off + window
        keep = band if keep is None else jnp.logical_and(keep, band)
    return keep


def _iota_diff_t(keys, queries):
    """(keys, queries) of local query - local key (lane - sublane): with
    one scalar it is the whole mask of a transposed score tile whose
    positions the grid implies."""
    return (jax.lax.broadcasted_iota(jnp.int32, (keys, queries), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (keys, queries), 0))


def _pos_needed(qp, kp, causal, window):
    """Scalar predicate from traced positions: does this (Q rows, KV
    columns) pair intersect the causal triangle ∩ window band at all?"""
    needed = jnp.max(qp) >= jnp.min(kp) if causal else None
    if window:
        in_band = jnp.max(kp) > jnp.min(qp) - window
        needed = in_band if needed is None else jnp.logical_and(needed,
                                                                in_band)
    return needed


def _safe(stat):
    """Fully-masked rows carry NEG_INF statistics; exp(s - NEG_INF) would
    be exp(0) = 1 for their masked entries — subtract 0 so p stays 0."""
    return jnp.where(stat <= NEG_INF / 2, 0.0, stat)


def _dispatch(update, *, major, unit, masks, needed_at, off, offsets,
              segments_of):
    """Hand ``update`` the segments of the step's resident block that it
    has to enter. Nothing masks: the whole block, plain. Traced positions
    (``needed_at`` given): no static diagonal, so every ``unit`` chunk
    under its own predicate, masked from the positions themselves.
    Else the static specialisations of :func:`_static_dispatch`."""
    if not masks:
        update(((0, major, None),))
    elif needed_at is not None:
        for c in range(0, major, unit):
            pl.when(needed_at(c))(functools.partial(update,
                                                    ((c, c + unit, 0),)))
    else:
        _static_dispatch(off, offsets, segments_of, update)


def _kv_dispatch(update, q_ref, k_ref, qpos_ref, kpos_ref, *, block_k, grid,
                 causal, window):
    """Forward, dQ and the fused backward (grid axes 1 and 2 count Q tiles
    and major blocks in all three): the key segments of the resident K/V
    block that this step's Q tile has to meet."""
    block_q, major = q_ref.shape[1], k_ref.shape[1]
    _dispatch(
        update, major=major, unit=block_k, masks=causal or bool(window),
        needed_at=None if qpos_ref is None else lambda c: _pos_needed(
            qpos_ref[...], kpos_ref[c:c + block_k, :], causal, window),
        off=pl.program_id(1) * block_q - pl.program_id(2) * major,
        offsets=[i * block_q - j * major
                 for i in range(grid[0]) for j in range(grid[1])],
        segments_of=lambda off: _kv_segments(off, major, block_q, block_k,
                                             causal, window))


class FetchPlan(NamedTuple):
    steps: int    # grid steps of one head: tiles x streamed major blocks
    entered: int  # of those, the ones the dispatch enters
    fetched: int  # major blocks the walk fetches: changes of the index

    def __str__(self):
        return f"steps={self.entered}/{self.steps} fetches={self.fetched}"


class BlockMap:
    """Which major block of its streamed side grid step (i, j) FETCHES: its
    own, j, where the dispatch enters it, else the nearest one that tile i
    enters, so the empty steps of a tile repeat an index and Pallas copies
    nothing for them. The map is a closed form a traced grid index can go
    through (an index map holds no table): tile i, ``tile`` positions from
    i x tile on, meets the streamed positions from ``back`` before its
    first to ``ahead`` past it (None: to the sequence's end), so j is
    clamped into the major blocks that hold them. Built, it is held against
    the dispatch itself, every step of the grid: ``segments_at(i, j)`` is
    what the dispatch hands the step, empty where it reads nothing of its
    block, so the steps that read a block and the steps given their own
    cannot drift apart. With nothing to skip (no mask, one major block) the
    map is j itself."""

    def __init__(self, n_tiles, n_major, segments_at, *, tile, major, back,
                 ahead):
        self.shape = (n_tiles, n_major)
        self.tile, self.major, self.back, self.ahead = tile, major, back, ahead
        self.enters = [[bool(segments_at(i, j)) for j in range(n_major)]
                       for i in range(n_tiles)]
        self.identity = all(map(all, self.enters))
        for i, row in enumerate(self.enters):
            for j, entered in enumerate(row):
                fetched = self(i, j)
                assert fetched == j if entered else row[fetched], (i, j)

    def __call__(self, i, j):
        if self.identity:
            return j
        least, most, div = (max, min, operator.floordiv) \
            if isinstance(i, int) else (jnp.maximum, jnp.minimum, jax.lax.div)
        first = i * self.tile
        if self.back is not None:
            j = least(j, div(least(first - self.back, 0), self.major))
        if self.ahead is not None:
            j = most(j, div(first + self.ahead, self.major))
        return j

    def plan(self) -> FetchPlan:
        n_tiles, n_major = self.shape
        walk = [self(i, j) for i in range(n_tiles) for j in range(n_major)]
        return FetchPlan(len(walk), sum(map(sum, self.enters)),
                         1 + sum(a != b for a, b in zip(walk, walk[1:])))


def block_map(Sq, Sk, tiles, *, causal, window, has_pos=False,
              streams="kv") -> BlockMap:
    """The one :class:`BlockMap` rule of all four calls. ``streams`` "kv":
    the forward's grid (Q tiles x major blocks of K and V: forward, fused
    backward, split dQ), a Q tile meeting the keys from ``window`` - 1
    before its first row to its last row; "q": the split dK/dV's (KV tiles
    x major blocks of Q, dO, lse and delta), a KV tile meeting the queries
    from its first column to ``window`` - 1 past its last. Traced positions
    (the ring's chunk entry) have no static range: every step is given its
    own block."""
    block_q, block_k, major_q, major_k = tiles
    if streams == "kv":
        shape, tile, major, segments = (
            (Sq // block_q, Sk // major_k), block_q, major_k, _kv_segments)
        back = window - 1 if window else None
        ahead = tile - 1 if causal else None
    else:
        shape, tile, major, segments = (
            (Sk // block_k, Sq // major_q), block_k, major_q, _q_segments)
        back = 0 if causal else None
        ahead = tile + window - 2 if window else None
    static = (causal or bool(window)) and not has_pos
    return BlockMap(*shape, lambda t, m: not static or segments(
        t * tile - m * major, major, block_q, block_k, causal, window),
        tile=tile, major=major, back=back, ahead=ahead)


def call_fetch_plan(q, k, *, causal: bool, window: int = 0) -> FetchPlan:
    """A head's grid in the forward and the fused backward of a
    full-sequence call under the tile rule, and the K and V blocks its maps
    fetch — what the dispatch's resolution line prints."""
    S = q.shape[1]
    tiles = tile_sizes(S, k.shape[1], q.shape[3], q.dtype.itemsize)
    return block_map(S, k.shape[1], tiles, causal=causal,
                     window=window).plan()


def _unpack(refs, n_inputs, has_pos):
    """(inputs, qpos_ref, kpos_ref, outputs and scratch): the position
    refs follow the inputs when the call has them."""
    refs = list(refs)
    ins, rest = refs[:n_inputs], refs[n_inputs:]
    if has_pos:
        return ins, rest[0], rest[1], rest[2:]
    return ins, None, None, rest


def _scores_t(q, kb, mask, qpos, kpos, *, scale, causal, window):
    """One segment's scores, TRANSPOSED: (keys, queries). ``mask`` None:
    wholly inside, no mask code. Else the keep-mask comes from the traced
    positions (``qpos`` a (1, queries) row, ``kpos`` a (keys, 1) column)
    or, grid-implied, from lane - sublane against the scalar ``mask``."""
    st = _dot(kb, q, _NT) * scale
    if mask is None:
        return st
    if qpos is not None:
        keep = _keep(qpos - kpos, 0, causal, window)
    else:
        keep = _keep(_iota_diff_t(*st.shape), mask, causal, window)
    return jnp.where(keep, st, NEG_INF)


# ================================================================= forward

def _fwd_kernel(*refs, block_k, grid, direct, causal, scale, window,
                has_pos):
    """Grid (BH, nq, n_major): one (block_q, D) output tile; each step
    meets its resident (major, D) K and V in ONE softmax update over the
    key segments it has to enter. With a single major block and static
    positions the update writes the output itself and no state is kept."""
    (q_ref, k_ref, v_ref), qpos_ref, kpos_ref, (o_ref, lse_ref, *state) = \
        _unpack(refs, 3, has_pos)
    masking = dict(causal=causal, window=window)
    kmi = pl.program_id(2)

    def finish(m, l, acc_t):
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
        o_ref[0] = (acc_t / l_safe).T.astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l_safe)

    if not direct:
        acc_ref, m_ref, l_ref = state

        @pl.when(kmi == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    def update(segs):
        q = q_ref[0]  # (block_q, D)
        scores = [_scores_t(q, k_ref[0, c0:c1, :], mask,
                            qpos_ref[...] if has_pos else None,
                            kpos_ref[c0:c1, :] if has_pos else None,
                            scale=scale, **masking)
                  for c0, c1, mask in segs]  # (c1 - c0, block_q) each
        m_new = functools.reduce(jnp.maximum, (
            jnp.max(st, axis=0, keepdims=True) for st in scores))
        if not direct:
            m_new = jnp.maximum(m_ref[...], m_new)  # (1, block_q)
        m_safe = _safe(m_new)
        probs = [jnp.exp(st - m_safe) for st in scores]
        l_new = functools.reduce(operator.add, (
            jnp.sum(pt, axis=0, keepdims=True) for pt in probs))
        acc = functools.reduce(operator.add, (  # (D, block_q) = V^T P^T
            _dot(v_ref[0, c0:c1, :], pt, _TN)
            for (c0, c1, _), pt in zip(segs, probs)))
        if direct:
            finish(m_new, l_new, acc)
        else:
            alpha = jnp.exp(m_ref[...] - m_safe)
            acc_ref[...] = acc_ref[...] * alpha + acc
            l_ref[...] = l_ref[...] * alpha + l_new
            m_ref[...] = m_new

    _kv_dispatch(update, q_ref, k_ref, qpos_ref, kpos_ref, block_k=block_k,
                 grid=grid, **masking)

    if not direct:
        @pl.when(kmi == pl.num_programs(2) - 1)
        def _finalize():
            finish(m_ref[...], l_ref[...], acc_ref[...])


def _fwd(q3, k3, v3, q_pos=None, kv_pos=None, *, causal, scale, tiles,
         window, interpret, out_dtype=None):
    BH, Sq, D = q3.shape
    Sk, Dv = k3.shape[1], v3.shape[2]
    # GQA without HBM expansion (ROADMAP kernel follow-up): q3 is flattened
    # batch-major with heads in order, so q row b = (batch·Hkv + kvh)·rep + r
    # and its KV row is simply b // rep — an index_map, not a materialized
    # repeat. rep == 1 is the MHA/pre-expanded case (identity map).
    rep = BH // k3.shape[0]
    block_q, block_k, _, major = tiles
    grid = (Sq // block_q, Sk // major)
    has_pos = q_pos is not None
    out_shape = [
        jax.ShapeDtypeStruct((BH, Sq, Dv), out_dtype or q3.dtype),  # O
        jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),  # LSE, a row a head
    ]
    # one resident block and static positions: the step writes its output
    # itself and keeps no running state
    direct = grid[1] == 1 and not has_pos
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, grid=grid, direct=direct,
        causal=causal, scale=scale, window=window, has_pos=has_pos,
    )
    fetched = block_map(Sq, Sk, tiles, causal=causal, window=window,
                        has_pos=has_pos)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, major, D),
                     lambda b, i, j: (b // rep, fetched(i, j), 0)),
        pl.BlockSpec((1, major, Dv),
                     lambda b, i, j: (b // rep, fetched(i, j), 0)),
    ]
    args = [q3, k3, v3]
    if has_pos:
        # i32 positions, shared across the BH grid axis: queries a
        # (1, Sq) row, keys an (Sk, 1) column, as the scores lie
        in_specs += [pl.BlockSpec((1, block_q), lambda b, i, j: (0, i)),
                     pl.BlockSpec((major, 1), lambda b, i, j: (j, 0))]
        args += [q_pos, kv_pos]
    return pl.pallas_call(
        kernel,
        grid=(BH, *grid),
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        scratch_shapes=[] if direct else [
            pltpu.VMEM((Dv, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
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
# All of it on transposed tiles (P^T, dP^T = V dO^T, dS^T), so dV and dK
# are plain products and dQ^T = K^T dS^T is transposed once on the way out,
# where ``scale`` multiplies it too.

def _p_ds_t(q, kb, vb, do, lse, delta, mask, qpos, kpos, **scoring):
    """One segment's P^T and dS^T, (keys, queries) each, rebuilt from the
    saved lse: what every backward kernel starts a score tile with."""
    pt = jnp.exp(_scores_t(q, kb, mask, qpos, kpos, **scoring) - lse)
    return pt, pt * (_dot(vb, do, _NT) - delta)


def _bwd_dq_kernel(*refs, block_k, grid, direct, causal, scale, window,
                   has_pos):
    """Grid (BH, nq, n_major), as the forward: one (block_q, D) dQ tile."""
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), qpos_ref, kpos_ref,
     (dq_ref, *state)) = _unpack(refs, 6, has_pos)
    masking = dict(causal=causal, window=window)
    kmi = pl.program_id(2)

    def finish(acc_t):
        dq_ref[0] = (acc_t * scale).T.astype(dq_ref.dtype)

    if not direct:
        acc_ref, = state

        @pl.when(kmi == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(segs):
        q = q_ref[0]
        do = do_ref[0]
        lse = _safe(lse_ref[0])  # lse, delta: (1, block_q)
        delta = delta_ref[0]
        parts = []
        for c0, c1, mask in segs:
            kb = k_ref[0, c0:c1, :]
            vb = v_ref[0, c0:c1, :]
            _, dst = _p_ds_t(q, kb, vb, do, lse, delta, mask,
                             qpos_ref[...] if has_pos else None,
                             kpos_ref[c0:c1, :] if has_pos else None,
                             scale=scale, **masking)
            parts.append(_dot(kb, dst, _TN))  # (D, block_q) = K^T dS^T
        acc = functools.reduce(operator.add, parts)
        if direct:
            finish(acc)
        else:
            acc_ref[...] += acc

    _kv_dispatch(update, q_ref, k_ref, qpos_ref, kpos_ref, block_k=block_k,
                 grid=grid, **masking)

    if not direct:
        @pl.when(kmi == pl.num_programs(2) - 1)
        def _fin():
            finish(acc_ref[...])


def _bwd_dkv_kernel(*refs, block_q, grid, rep, direct, causal, scale,
                    window, has_pos):
    """Grid (B·Hkv, nk, rep, n_major): one (block_k, D) dK/dV tile; each
    step meets the query segments it has to enter of its resident
    (major, D) Q and dO and (1, major) lse and delta. The rep axis
    revisits the SAME KV tile for each of the rep query heads sharing it
    (GQA) — first visit (r==0, first major block) zeroes the accumulators,
    every visit adds, the last writes out. rep==1 is MHA, and with one
    major block besides the step writes dK/dV itself, no state kept."""
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), qpos_ref, kpos_ref,
     (dk_ref, dv_ref, *state)) = _unpack(refs, 6, has_pos)
    major, block_k = q_ref.shape[1], k_ref.shape[1]
    masking = dict(causal=causal, window=window)
    ki = pl.program_id(1)
    r = pl.program_id(2)
    qmi = pl.program_id(3)

    def finish(dk, dv):
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    if not direct:
        dk_acc, dv_acc = state

        @pl.when((qmi == 0) & (r == 0))
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    def update(segs):
        kb = k_ref[0]
        vb = v_ref[0]
        dks, dvs = [], []
        for r0, r1, mask in segs:
            q = q_ref[0, r0:r1, :]
            do = do_ref[0, r0:r1, :]
            pt, dst = _p_ds_t(  # (block_k, r1 - r0) each
                q, kb, vb, do, _safe(lse_ref[0, :, r0:r1]),
                delta_ref[0, :, r0:r1], mask,
                qpos_ref[:, r0:r1] if has_pos else None,
                kpos_ref[...] if has_pos else None, scale=scale, **masking)
            dvs.append(_dot(pt, do, _NN))  # (block_k, D)
            dks.append(_dot(dst, q, _NN))  # (block_k, D)
        dk = functools.reduce(operator.add, dks)
        dv = functools.reduce(operator.add, dvs)
        if direct:
            finish(dk, dv)
        else:
            dk_acc[...] += dk
            dv_acc[...] += dv

    _dispatch(
        update, major=major, unit=block_q, masks=causal or bool(window),
        needed_at=None if not has_pos else lambda r0: _pos_needed(
            qpos_ref[:, r0:r0 + block_q], kpos_ref[...], causal, window),
        off=ki * block_k - qmi * major,
        offsets=[j * block_k - i * major
                 for j in range(grid[0]) for i in range(grid[1])],
        segments_of=lambda off: _q_segments(off, major, block_q, block_k,
                                            causal, window))

    if not direct:
        @pl.when((qmi == pl.num_programs(3) - 1) & (r == rep - 1))
        def _fin():
            finish(dk_acc[...], dv_acc[...])


def _bwd_fused_kernel(*refs, block_k, grid, rep, direct, causal, scale,
                      window, has_pos):
    """Grid (B·Hkv, nq, n_major, rep): the forward's grid under a KV head,
    the head's rep query heads innermost. A step meets ONE query head's
    (block_q, D) Q and dO tile with the key segments it has to enter of the
    resident (major, D) K and V, builds S^T, P^T and dS^T of each once and
    runs the three output products from them. The rep steps of a (Q tile,
    major block) pair share K and V (one fetch for all of them) and the
    pair's offset, so they enter the same segments. Q, dO, lse, delta and
    dQ are blocks of all rep heads' tile, fetched and written once a Q
    tile; dQ is written by its step outright with one major block, else
    summed over them in a (rep, D, block_q) scratch. dK and dV of the KV
    head's WHOLE sequence stay in float32 VMEM, (n_major, major, D), under
    every Q tile and query head that reads the head: its first step zeroes
    them, every step adds its segments' rows, its last writes them out."""
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), qpos_ref, kpos_ref,
     (dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *state)) = \
        _unpack(refs, 6, has_pos)
    major = k_ref.shape[1]
    masking = dict(causal=causal, window=window)
    qi, kmi, r = (pl.program_id(a) for a in (1, 2, 3))
    n_major = grid[1]

    @pl.when((qi == 0) & (kmi == 0) & (r == 0))
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if not direct:
        dq_acc, = state

        @pl.when(kmi == 0)
        def _init():
            dq_acc[r] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    def update(segs):
        q = q_ref[r]
        do = do_ref[r]
        lse = _safe(lse_ref[r])  # lse, delta: (1, block_q)
        delta = delta_ref[r]
        parts = []
        for c0, c1, mask in segs:
            kb = k_ref[0, c0:c1, :]
            vb = v_ref[0, c0:c1, :]
            pt, dst = _p_ds_t(  # (c1 - c0, block_q) each
                q, kb, vb, do, lse, delta, mask,
                qpos_ref[...] if has_pos else None,
                kpos_ref[c0:c1, :] if has_pos else None,
                scale=scale, **masking)
            dv_acc[kmi, c0:c1, :] += _dot(pt, do, _NN)  # (c1 - c0, Dv)
            dk_acc[kmi, c0:c1, :] += _dot(dst, q, _NN)  # (c1 - c0, D)
            parts.append(_dot(kb, dst, _TN))  # (D, block_q) = K^T dS^T
        acc = functools.reduce(operator.add, parts)
        if direct:
            dq_ref[r] = (acc * scale).T.astype(dq_ref.dtype)
        else:
            dq_acc[r] += acc

    _kv_dispatch(update, q_ref, k_ref, qpos_ref, kpos_ref, block_k=block_k,
                 grid=grid, **masking)

    if not direct:
        @pl.when(kmi == n_major - 1)
        def _fin_dq():
            dq_ref[r] = (dq_acc[r] * scale).T.astype(dq_ref.dtype)

    @pl.when((qi == grid[0] - 1) & (kmi == n_major - 1) & (r == rep - 1))
    def _fin_dkv():
        for m in range(n_major):
            rows = slice(m * major, (m + 1) * major)
            dk_ref[0, rows, :] = (dk_acc[m] * scale).astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_acc[m].astype(dv_ref.dtype)


# What the fused kernel keeps in VMEM beyond a step's own blocks. A KV
# head's dK and dV: float32 accumulators, Sk x (D + Dv) x 4 bytes, and the
# output blocks they are written to, which Pallas buffers twice. A Q
# tile's blocks for all rep query heads of the KV head: Q, dO and dQ twice
# buffered and dQ's float32 sum over the major blocks. Taken where that
# fits FUSED_RESIDENT_BYTES: 21 MB at latent attention's (8192, 192 + 128)
# bf16, 16.8 + 9.4 MB at (8192, 128 + 128) with nine heads a KV head, 1.6 MB
# at GPT-2's (1024, 64 + 64); 16384 keys at D 128 no longer fit, nor 8192
# under more than 16 heads a KV head. The scoped limit a call asks for
# is that and _STEP_VMEM for what a step streams and computes (K and V
# twice buffered 2-2.5 MiB, a few (2048, 512) float32 score segments of 4
# MiB each): the v5e has 128 MiB.
FUSED_RESIDENT_BYTES = 32 << 20
_STEP_VMEM = 32 << 20


class BackwardPlan(NamedTuple):
    fused: bool    # one kernel (dQ with dK/dV), else the dQ and dK/dV pair
    resident: int  # bytes the fused kernel keeps in VMEM under a KV head

    def __str__(self):
        return (f"bwd={'fused' if self.fused else 'split'} "
                f"resident={self.resident / 1e6:.1f}MB")


def backward_plan(Sk: int, D: int, Dv: int, itemsize: int, *, rep: int = 1,
                  block_q: int = DEFAULT_BLOCK_Q) -> BackwardPlan:
    """Which backward a call gets, from what it can see: the fused kernel
    where what it keeps under a KV head (dK and dV of the head's keys, a Q
    tile of its ``rep`` query heads) fits FUSED_RESIDENT_BYTES of VMEM."""
    resident = (Sk * (D + Dv) * (4 + 2 * itemsize)
                + rep * block_q * (2 * (2 * D + Dv) * itemsize + 4 * D))
    return BackwardPlan(resident <= FUSED_RESIDENT_BYTES, resident)


def call_backward_plan(q, k, v) -> BackwardPlan:
    """:func:`backward_plan` of a (B, S, H, D) call — what the dispatch's
    resolution line prints."""
    block_q = tile_sizes(q.shape[1], k.shape[1], q.shape[3],
                         q.dtype.itemsize).block_q
    return backward_plan(k.shape[1], k.shape[3], v.shape[3], k.dtype.itemsize,
                         rep=q.shape[2] // k.shape[2], block_q=block_q)


def _bwd(q3, k3, v3, o3, lse, do3, q_pos=None, kv_pos=None, *, causal,
         scale, tiles, window, interpret, dlse=None):
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (BH, 1, Sq), a row a head as lse
    if dlse is not None:
        # Chunk entry: the lse output has its own cotangent. With
        # lse = logsumexp(s), d lse/d s = p, so ds gains +p·dlse — which
        # folds into the flash2 formula as delta' = delta − dlse.
        delta = delta - dlse
    plan = backward_plan(k3.shape[1], k3.shape[2], v3.shape[2],
                         k3.dtype.itemsize, rep=q3.shape[0] // k3.shape[0],
                         block_q=tiles.block_q)
    form = functools.partial(_bwd_fused, resident=plan.resident) \
        if plan.fused else _bwd_split
    return form([q3, k3, v3, do3, lse, delta], q_pos, kv_pos, tiles=tiles,
                interpret=interpret,
                static=dict(causal=causal, scale=scale, window=window,
                            has_pos=q_pos is not None))


def _bwd_fused(args, q_pos, kv_pos, *, tiles, interpret, static, resident):
    q3, k3, v3 = args[:3]
    BH, Sq, D = q3.shape
    Sk, Dv = k3.shape[1], v3.shape[2]
    rep = BH // k3.shape[0]  # GQA group size (see _fwd); 1 = MHA
    block_q, block_k, _, major = tiles
    grid = (Sq // block_q, Sk // major)
    has_pos = static["has_pos"]
    direct = grid[1] == 1 and not has_pos  # dQ: as the forward

    def group(width):
        # the tile of all rep query heads of KV row b: rows b·rep ... of
        # the flattened heads, the inverse of the forward's b // rep map
        return pl.BlockSpec((rep, block_q, width),
                            lambda b, i, j, r: (b, i, 0))

    row = pl.BlockSpec((rep, 1, block_q), lambda b, i, j, r: (b, 0, i))
    fetched = block_map(Sq, Sk, tiles, has_pos=has_pos,
                        causal=static["causal"], window=static["window"])
    in_specs = [
        group(D),
        pl.BlockSpec((1, major, D),
                     lambda b, i, j, r: (b, fetched(i, j), 0)),
        pl.BlockSpec((1, major, Dv),
                     lambda b, i, j, r: (b, fetched(i, j), 0)),
        group(Dv), row, row,
    ]
    if has_pos:
        in_specs += [pl.BlockSpec((1, block_q), lambda b, i, j, r: (0, i)),
                     pl.BlockSpec((major, 1), lambda b, i, j, r: (j, 0))]
        args = args + [q_pos, kv_pos]
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, block_k=block_k, grid=grid,
                          rep=rep, direct=direct, **static),
        grid=(BH // rep, *grid, rep),
        in_specs=in_specs,
        out_specs=[
            group(D),
            pl.BlockSpec((1, Sk, D), lambda b, i, j, r: (b, 0, 0)),
            pl.BlockSpec((1, Sk, Dv), lambda b, i, j, r: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q3.shape, q3.dtype),
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((grid[1], major, D), jnp.float32),
                        pltpu.VMEM((grid[1], major, Dv), jnp.float32)]
        + ([] if direct else [pltpu.VMEM((rep, D, block_q), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=resident + _STEP_VMEM,
        ),
        interpret=interpret,
    )(*args)


def _bwd_split(args, q_pos, kv_pos, *, tiles, interpret, static):
    q3, k3, v3 = args[:3]
    BH, Sq, D = q3.shape
    Sk, Dv = k3.shape[1], v3.shape[2]
    rep = BH // k3.shape[0]
    block_q, block_k, major_q, major_k = tiles
    has_pos = static["has_pos"]

    dq_grid = (Sq // block_q, Sk // major_k)
    dq_direct = dq_grid[1] == 1 and not has_pos  # as the forward
    skips = dict(causal=static["causal"], window=static["window"],
                 has_pos=has_pos)
    kv_of = block_map(Sq, Sk, tiles, **skips)
    dq_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, major_k, D),
                     lambda b, i, j: (b // rep, kv_of(i, j), 0)),
        pl.BlockSpec((1, major_k, Dv),
                     lambda b, i, j: (b // rep, kv_of(i, j), 0)),
        pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
    ]
    dq_args = list(args)
    if has_pos:
        dq_in_specs += [pl.BlockSpec((1, block_q), lambda b, i, j: (0, i)),
                        pl.BlockSpec((major_k, 1), lambda b, i, j: (j, 0))]
        dq_args += [q_pos, kv_pos]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, grid=dq_grid,
                          direct=dq_direct, **static),
        grid=(BH, *dq_grid),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[] if dq_direct
        else [pltpu.VMEM((D, block_q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*dq_args)

    # dK/dV grid (B·Hkv, nk, rep, n_major): q-side rows for KV row b are
    # b·rep + r — the inverse of the forward's b // rep map.
    dkv_grid = (Sk // block_k, Sq // major_q)
    dkv_direct = dkv_grid[1] == 1 and rep == 1 and not has_pos
    q_of = block_map(Sq, Sk, tiles, streams="q", **skips)
    rows = pl.BlockSpec((1, 1, major_q),
                        lambda b, j, r, i: (b * rep + r, 0, q_of(j, i)))
    dkv_in_specs = [
        pl.BlockSpec((1, major_q, D),
                     lambda b, j, r, i: (b * rep + r, q_of(j, i), 0)),
        pl.BlockSpec((1, block_k, D), lambda b, j, r, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, Dv), lambda b, j, r, i: (b, j, 0)),
        pl.BlockSpec((1, major_q, Dv),
                     lambda b, j, r, i: (b * rep + r, q_of(j, i), 0)),
        rows, rows,
    ]
    dkv_args = list(args)
    if has_pos:
        dkv_in_specs += [
            pl.BlockSpec((1, major_q), lambda b, j, r, i: (0, i)),
            pl.BlockSpec((block_k, 1), lambda b, j, r, i: (j, 0)),
        ]
        dkv_args += [q_pos, kv_pos]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, grid=dkv_grid,
                          rep=rep, direct=dkv_direct, **static),
        grid=(BH // rep, dkv_grid[0], rep, dkv_grid[1]),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, r, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, j, r, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ],
        scratch_shapes=[] if dkv_direct
        else [pltpu.VMEM((block_k, D), jnp.float32),
              pltpu.VMEM((block_k, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*dkv_args)
    return dq, dk, dv


# ============================================================== public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, scale, tiles, interpret, window):
    o, _ = _fwd(q3, k3, v3, causal=causal, scale=scale, tiles=tiles,
                window=window, interpret=interpret)
    return o


def _flash_fwd(q3, k3, v3, causal, scale, tiles, interpret, window):
    o, lse = _fwd(q3, k3, v3, causal=causal, scale=scale, tiles=tiles,
                  window=window, interpret=interpret)
    # tagged HERE, inside the custom-vjp forward, so that lse, which is no
    # output, can be kept too; the identity outside jax.checkpoint
    o = checkpoint_name(o, FLASH_RESIDUALS_NAME)
    lse = checkpoint_name(lse, FLASH_RESIDUALS_NAME)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, scale, tiles, interpret, window, res, do3):
    q3, k3, v3, o3, lse = res
    dq, dk, dv = _bwd(q3, k3, v3, o3, lse, do3, causal=causal, scale=scale,
                      tiles=tiles, window=window, interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    window: int = 0,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    block_k_major: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """(B, S, H, D) attention via the Pallas kernel. GQA (Hkv < H,
    H % Hkv == 0) is NATIVE: K/V stay at Hkv heads in HBM and the kernel's
    BlockSpec index_map (q row b → KV row b // rep) shares each KV tile
    across its query group — no expanded copy is ever materialised
    (forward reads H/Hkv x less K/V bandwidth than an expand-first
    design). ``window`` > 0 restricts each query to its trailing
    ``window`` keys (requires causal — enforced upstream). Tile sizes
    default to :func:`tile_sizes`' rule; tests pass their own.

    V's head dim may differ from Q's and K's (latent attention: 192-deep
    scores beside 128-deep values); the output has V's."""
    if k.shape[:3] != v.shape[:3] or q.shape[3] != k.shape[3]:
        raise ValueError(
            f"q/k/v shapes do not fit: {q.shape}, {k.shape}, {v.shape}")
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H and (Hkv == 0 or H % Hkv != 0):
        raise ValueError(
            f"invalid GQA ratio: {H} query heads over {Hkv} KV heads")
    tiles = tile_sizes(S, S, D, q.dtype.itemsize, block_q=block_q,
                       block_k=block_k, block_k_major=block_k_major)
    scale = float(1.0 / (D ** 0.5))

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], S, x.shape[3])

    o3 = _flash(to3(q), to3(k), to3(v), causal, scale, tiles, interpret,
                int(window))
    return o3.reshape(B, H, S, v.shape[3]).transpose(0, 2, 1, 3)


# ----------------------------------------------------- ring-chunk entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_chunk(q3, k3, v3, qp, kp, causal, scale, tiles, interpret,
                 window):
    o, lse = _fwd(q3, k3, v3, qp, kp, causal=causal, scale=scale,
                  tiles=tiles, window=window, interpret=interpret,
                  out_dtype=jnp.float32)
    return o, lse


def _flash_chunk_fwd(q3, k3, v3, qp, kp, causal, scale, tiles, interpret,
                     window):
    o, lse = _flash_chunk(q3, k3, v3, qp, kp, causal, scale, tiles,
                          interpret, window)
    return (o, lse), (q3, k3, v3, qp, kp, o, lse)


def _flash_chunk_bwd(causal, scale, tiles, interpret, window, res, ct):
    q3, k3, v3, qp, kp, o3, lse = res
    do3, dlse = ct
    dq, dk, dv = _bwd(q3, k3, v3, o3, lse, do3.astype(jnp.float32), qp, kp,
                      causal=causal, scale=scale, tiles=tiles,
                      window=window, interpret=interpret, dlse=dlse)
    zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # noqa: E731
    return dq, dk, dv, zero(qp), zero(kp)


_flash_chunk.defvjp(_flash_chunk_fwd, _flash_chunk_bwd)


def flash_attention_chunk(q, k, v, q_pos, kv_pos, *, causal: bool,
                          window: int = 0,
                          block_q: int | None = None,
                          block_k: int | None = None,
                          block_k_major: int | None = None,
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
    tiles = tile_sizes(Sq, Sk, D, q.dtype.itemsize, block_q=block_q,
                       block_k=block_k, block_k_major=block_k_major)
    scale = float(1.0 / (D ** 0.5))
    qp = q_pos.astype(jnp.int32).reshape(1, Sq)  # a row: queries on lanes
    kp = kv_pos.astype(jnp.int32).reshape(Sk, 1)  # a column: keys on sublanes

    def to3(x):  # per-tensor head count: k/v stay at Hkv rows (GQA)
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2],
                                               x.shape[1], D)

    o3, lse = _flash_chunk(to3(q), to3(k), to3(v), qp, kp, causal, scale,
                           tiles, interpret, int(window))
    o = o3.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return o, lse.reshape(B, H, Sq)
