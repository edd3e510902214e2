"""Pallas TPU grouped product: rows sorted by group, each group through its
own matrix (the held experts' bank of ops/moe.py ``_grouped_bank``).

    out[r] = rows[r] @ weights[g]    for the g with starts[g] <= r < ends[g]

``rows`` (R, K) and ``weights`` (G, K, N) in the compute dtype, ``sizes``
(G,) int32 the rows of each group from row 0 on, float32 sums, float32 out:
what ``jax.lax.ragged_dot(..., preferred_element_type=float32)`` computes.
On a TPU that call is XLA's own kernel, whose tiles nobody here chose: 29 %
of the MXU's peak at 8 groups of ~2048 rows through 2048 x 1792 (PERF.md
section 6, PR 46), and over every row of the bound, of which half to three
quarters belong to no group (``HeldExpertsSpec.capacity_factor`` 2 to 4).

The scheme is megablox's (``jax.experimental.pallas.ops.tpu.megablox``, the
installed JAX's reference design; this file is the repo's own, because that
package moves and the contract below is this repo's). A table from the
running sum of ``sizes`` (:func:`group_steps`), prefetched into scalar
memory, maps each step of a DYNAMIC grid axis to (row tile, group): a tile
inside one group is visited once, a tile that straddles a boundary once a
group with its other rows masked, and a tile wholly past ``sum(sizes)`` is
no step at all, so it costs nothing. Two kernels:

- ``grouped_matmul_rows``: one (tile m, tile n) block of a row-shaped result
  a step, the contraction whole (no accumulator), the group's weights
  resident along the row tiles (columns outermost). Forward, and ``d rows``
  as the same kernel against the weights read transposed.
- ``grouped_matmul_weights``: ``d weights``, the group-contracting form
  ``rows[g]^T @ cotangent[g]``: one (wk, wn) block of a group's matrix
  accumulated in VMEM over that group's row tiles (steps innermost).

A group of no rows has one step of its own in the table, every row masked:
the weights kernel writes its zeros there, and the row kernel, which needs
no such step, pays one idle tile for sharing the table (megablox keeps two).

THE CONTRACT. No row at or past ``sum(sizes)`` enters any result, forward
or backward: the row kernel's result rows depend on their own row alone and
are stored under the group's mask, the weights kernel zeroes the rows of
BOTH operands outside the group in a boundary tile. What the row-shaped
results (the output, ``d rows``) hold AT those rows is whatever the buffer
held: the caller zeroes them where they leave it (``_grouped_bank``: once on
the way in, whose transpose covers ``d rows``, once on the way out). The
residuals are ``ragged_dot``'s: rows, weights, sizes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_train_tpu.ops import attention, flash_attention
from pytorch_distributed_train_tpu.ops.flash_attention import (
    _NN,
    _NT,
    _TN,
    _dot,
)

_F32 = jnp.float32
# The largest step of the rule below holds some 36 MiB (its blocks twice,
# for the pipeline); a kernel gets 16 MiB unasked, a v5e's core has 128.
_VMEM_LIMIT = 64 << 20
# Elements of a weights block the row kernel keeps resident (twice), and of
# the weights kernel's output block (a float32 accumulator and the result
# twice): a group's whole matrix wherever it has no more. Read on a v5e at
# 8 groups of ~2048 rows through 2048 x 1792 (PERF.md section 6, PR 46):
# the row kernel 0.814 ms a call at whole columns against 0.829 at half of
# them, ``d rows`` 0.798 against 0.821, the weights kernel 0.839 at the
# whole matrix against 0.880 at half and 0.963 at a quarter.
_BLOCK = 4 << 20


class Tiles(NamedTuple):
    m: int       # rows a grid step, all three launches
    n: int       # columns of the forward's result a step (K whole)
    back_n: int  # columns of ``d rows`` a step (N whole)
    wk: int      # the weights kernel's output block: (wk, wn) of a
    wn: int      # group's (K, N)


def _fit(size: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``size`` and is at most ``cap``
    (128 at least); ``size`` itself where it is no multiple of 128 (a block
    may always span a whole dimension)."""
    return size if size % 128 else flash_attention._fit(size, cap, 128)


def tile_sizes(K: int, N: int, mean_rows: int) -> Tiles:
    """The one tile rule, from what a call can see: the matrices' shape and
    the rows a group has on average (static: tokens x top_k / experts)."""
    # Read on a v5e (PERF.md section 6, PR 46), forward / d rows / d weights
    # in ms a call: at ~2048 rows a group rows of 256 ran 0.814 / 0.798 /
    # 0.839, of 512 0.865 / 0.855 / 0.877, of 1024 1.003 / 1.005 / 1.580 (a
    # straddled tile is a whole tile's work a group); at ~768 rows a group
    # rows of 128 ran 0.361 / 0.376 / 0.396, of 256 0.366 / 0.381 / 0.407,
    # of 512 0.406 / 0.423 / 0.446. At ~205 rows a group, read THROUGH the
    # head-share cell's step (PERF.md section 6, PR 47): rows of 64 / 128 /
    # 256 a median step of 225.12 / 224.22 / 224.56 ms, where two seeds of
    # one tile stand 0.35 ms apart: a call there is bound by each group's
    # weights read once and its launch, so the rule has no arm under 128.
    m = 256 if mean_rows >= 1024 else 128
    wn = _fit(N, 2048)
    return Tiles(m, _fit(N, _BLOCK // K), _fit(K, _BLOCK // N),
                 _fit(K, _BLOCK // wn), wn)


def unsupported(K: int, N: int) -> str | None:
    """Why the kernels do not take a bank of (K, N) matrices, or None."""
    if not attention._on_tpu():
        return "the backend is not a TPU"
    if K % 128 or N % 128:
        return f"matrices of {K}x{N} are not whole tiles of 128"
    return None


# ``jit(inline=True)``: traced once a shape and process and replayed at every
# call site (ops/kda_inputs.py). The table is a few dozen small operations,
# and an expert layer asks for it seven times (a product's forward, its
# backward, the layer's ``stats``): traced anew each time they were a
# tenth of a second a layer and trace, 2.5 s of a run's set-up twice over
# in the short-convolution cell (PERF.md section 6, PR 46).
@functools.partial(jax.jit, static_argnums=(1, 2), inline=True)
def group_steps(sizes, rows: int, m: int):
    """The grid's table: (bounds (G + 1,), group (S,), tile (S,), steps ())
    int32, S = tiles + G - 1 the most steps any routing needs. Step s works
    on rows [tile[s] m, tile[s] m + m) for group[s], whose rows are
    [bounds[g], bounds[g + 1]); only the first ``steps`` entries are real.
    Tiles never step back, so a result block is revisited only by
    consecutive steps. A group of no rows gets one step, at the tile its
    rows would start in."""
    G = sizes.shape[0]
    tiles = -(-rows // m)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // m, tiles - 1)
    visits = jnp.where(sizes > 0, (ends - 1) // m - starts // m + 1, 1)
    upto = jnp.cumsum(visits)
    step = jnp.arange(tiles + G - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(step[:, None] >= upto, 1, dtype=jnp.int32),
                        G - 1)
    # a group's first tile against its first step (a compare and a sum over
    # (S, G): on a TPU a gather of G entries is G scalar operations)
    shift = jnp.sum(jnp.where(group[:, None] == jnp.arange(G),
                              first - (upto - visits), 0), 1)
    tile = jnp.minimum(step + shift, tiles - 1)
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return bounds, group, tile.astype(jnp.int32), upto[-1]


@functools.partial(jax.jit, static_argnums=(1, 2), inline=True)
def tile_visits_ratio(sizes, rows: int, m: int):
    """Steps of the row kernel over the whole tiles its real rows would
    fill, float32: 1.0 where no group boundary costs a visit."""
    steps = group_steps(sizes, rows, m)[3]
    whole = jnp.maximum(-(-jnp.sum(sizes) // m), 1)
    return steps.astype(_F32) / whole.astype(_F32)


def _in_group(bounds_ref, group_ref, tile_ref, s, m: int):
    """(whole, keep): is step ``s``'s row tile inside its group entirely,
    and which of its rows are, as a function of a block's shape."""
    g = group_ref[s]
    lo, hi = bounds_ref[g], bounds_ref[g + 1]
    first = tile_ref[s] * m

    def keep(shape):
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + first
        return (row >= lo) & (row < hi)

    return (first >= lo) & (first + m <= hi), keep


# ========================================================== row-shaped results

def _rows_kernel(bounds_ref, group_ref, tile_ref, x_ref, w_ref, o_ref, *,
                 dims):
    """Grid (column tiles, steps): the step's row tile times its group's
    weights block; rows of other groups keep what an earlier step wrote."""
    whole, keep = _in_group(bounds_ref, group_ref, tile_ref,
                            pl.program_id(1), o_ref.shape[0])
    w = w_ref[...]
    acc = _dot(x_ref[...].astype(w.dtype), w, dims)

    @pl.when(whole)
    def _all():
        o_ref[...] = acc.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(whole))
    def _some():
        o_ref[...] = jnp.where(keep(acc.shape), acc,
                               o_ref[...].astype(_F32)).astype(o_ref.dtype)


# ``jit(inline=True)`` round a launch: traced once a shape and process and
# replayed at every call site under that site's scopes (ops/kda_inputs.py).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7), inline=True)
def _rows_launch(x, w, table, m, n, transposed, out_dtype, interpret):
    """x (R, C) against w (G, C, n-tiled) -> (R, N); ``transposed``: against
    w (G, n-tiled, C) read as its transpose -> (R, K)."""
    bounds, group, tile, steps = table
    R, C = x.shape
    cols = w.shape[1] if transposed else w.shape[2]
    if transposed:
        w_spec = pl.BlockSpec((None, n, C),
                              lambda j, s, b, g, t: (g[s], j, 0))
    else:
        w_spec = pl.BlockSpec((None, C, n),
                              lambda j, s, b, g, t: (g[s], 0, j))
    return pl.pallas_call(
        functools.partial(_rows_kernel, dims=_NT if transposed else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(cols // n, steps),
            in_specs=[
                pl.BlockSpec((m, C), lambda j, s, b, g, t: (t[s], 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((m, n), lambda j, s, b, g, t: (t[s], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((R, cols), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="grouped_matmul_rows",
        interpret=interpret,
    )(bounds, group, tile, x, w)


# ================================================================= d weights

def _weights_kernel(bounds_ref, group_ref, tile_ref, x_ref, g_ref, o_ref,
                    acc_ref):
    """Grid (K tiles, N tiles, steps), steps innermost: x^T @ g over the
    step's row tile into the accumulator of the step's group, which leaves
    when the group does."""
    s, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ref[s]
    whole, keep = _in_group(bounds_ref, group_ref, tile_ref, s,
                            x_ref.shape[0])

    @pl.when((s == 0) | (group_ref[jnp.maximum(s - 1, 0)] != group))
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(whole)
    def _all():
        x = x_ref[...]
        acc_ref[...] += _dot(x, g_ref[...].astype(x.dtype), _TN)

    @pl.when(jnp.logical_not(whole))
    def _some():  # (a group of no rows: every row masked, zeros added)
        x, g = x_ref[...], g_ref[...]
        # both operands: 0 x NaN is NaN, and either may hold anything in a
        # row of no group
        xm = jnp.where(keep(x.shape), x.astype(_F32), 0.0).astype(x.dtype)
        gm = jnp.where(keep(g.shape), g.astype(_F32), 0.0).astype(x.dtype)
        acc_ref[...] += _dot(xm, gm, _TN)

    @pl.when((s == last) | (group_ref[jnp.minimum(s + 1, last)] != group))
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7), inline=True)
def _weights_launch(x, g, table, m, wk, wn, out_dtype, interpret):
    """x (R, K), g (R, N) -> (G, K, N): each group's x^T @ g."""
    bounds, group, tile, steps = table
    K, N = x.shape[1], g.shape[1]
    return pl.pallas_call(
        _weights_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(K // wk, N // wn, steps),
            in_specs=[
                pl.BlockSpec((m, wk), lambda i, j, s, b, g, t: (t[s], i)),
                pl.BlockSpec((m, wn), lambda i, j, s, b, g, t: (t[s], j)),
            ],
            out_specs=pl.BlockSpec((None, wk, wn),
                                   lambda i, j, s, b, g, t: (g[s], i, j)),
            scratch_shapes=[pltpu.VMEM((wk, wn), _F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bounds.shape[0] - 1, K, N),
                                       out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="grouped_matmul_weights",
        interpret=interpret,
    )(bounds, group, tile, x, g)


# ============================================================== entry point

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(rows, weights, sizes, tiles, interpret):
    return _grouped_fwd(rows, weights, sizes, tiles, interpret)[0]


def _grouped_fwd(rows, weights, sizes, tiles, interpret):
    table = group_steps(sizes, rows.shape[0], tiles.m)
    out = _rows_launch(rows, weights, table, tiles.m, tiles.n, False, _F32,
                       interpret)
    return out, (rows, weights, sizes)


def _grouped_bwd(tiles, interpret, res, ct):
    rows, weights, sizes = res
    table = group_steps(sizes, rows.shape[0], tiles.m)
    d_rows = _rows_launch(ct, weights, table, tiles.m, tiles.back_n, True,
                          rows.dtype, interpret)
    d_weights = _weights_launch(rows, ct, table, tiles.m, tiles.wk, tiles.wn,
                                weights.dtype, interpret)
    return d_rows, d_weights, np.zeros(sizes.shape, jax.dtypes.float0)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(rows, weights, sizes, *, mean_rows: int,
                   interpret: bool = False, tiles: Tiles | None = None):
    """(R, K) x (G, K, N) -> (R, N) float32 by ``sizes`` (G,) int32,
    differentiable in ``rows`` and ``weights`` (the module docstring's
    contract). ``mean_rows``: the rows a group has on average, static: it
    picks the row tile. Explicit ``tiles`` (tests, tuning) override the
    rule's."""
    K, N = weights.shape[1:]
    tiles = tiles or tile_sizes(K, N, mean_rows)
    if K % tiles.wk or N % tiles.n or K % tiles.back_n or N % tiles.wn \
            or tiles.m % 16:
        raise ValueError(
            f"grouped product tiles {tuple(tiles)} do not fit matrices of "
            f"{K}x{N}: n and wn must divide N, back_n and wk divide K, m be "
            "a multiple of 16")
    return _grouped(rows, weights, sizes.astype(jnp.int32), tiles, interpret)
