"""The KDA mixer's input shaping (models/hybrid.py ``KDAMixer``): what stands
between the four projections and the chunk core of ops/kda.py.

    q = unit(silu(conv(y_q, w_q))) d^-1/2     unit(y) = y rsqrt(sum_d y^2 + 1e-6)
    k = unit(silu(conv(y_k, w_k)))            conv(y, w)_t = sum_j w[j] y_{t-(K-1-j)}
    v =      silu(conv(y_v, w_v))             (depthwise, causal, zeros before t = 0)
    g = lower_bound sigmoid(exp(A_log) (a + dt_bias))      the bounded gate
    g = -exp(A_log) softplus(a + dt_bias)                  the unbounded one

all of it in float32 whatever the operands' dtype, one cast at the end. The
gate's form is the mixer's (``lower_bound`` a number, or None for the
report's own softplus gate, g in (-inf, 0]: ops/kda.py's two forms); ``a``
is whatever the mixer projected, full rank or two low-rank products.

Two paths, one algorithm (the rule of ops/kda.py: ``kda.unsupported`` says
why not, from what a call can see; no option, and ONE decision for the
shaping and the core). :func:`shape_inputs_xla` is the mathematics as
``jax.numpy`` spells it, under ``jax.checkpoint``: the CPU tests', the
rehearsals' and small heads' path, and the tests' ground truth. Left to XLA
on a TPU it was a fifth of the hybrid cell's step (PERF.md section 6, PR
38): the compiler kept the projections' outputs in float32 with the
SEQUENCE on the lanes, because the convolution's shifted slices and the
taps' gradients read along it, made three float32 round trips through HBM a
tensor and a pass of its own for every small gradient, and had to copy all
four results back to the rows the core's kernels take.

:func:`kda_inputs` is the Pallas kernel pair. Both kernels walk the core's
own view of its operands, ``(B, S, H d)`` in blocks of ``tile`` tokens by
``heads`` whole heads (ops/kda_kernel.py ``_fwd``), so what leaves the
forward kernel enters ``kda_fwd`` with no copy between them, and what
leaves ``kda_bwd`` enters the backward kernel the same way. The forward
kernel walks the tiles in order and keeps each stream's last rows in VMEM
for the next tile's convolution. The backward kernel walks them from the
last to the first: it recomputes its tile from the inputs (so the inputs
are the only residuals), reads the K - 1 rows before the tile through a
second block of the same operand, keeps the first rows of ``dy`` for the
tile before it (``dx_t`` collects ``w[j] dy_{t+(K-1-j)}``), and sums the
small gradients (the taps', ``dt_bias``', ``exp(A_log)``'s a channel) in a
float32 output block that stays in VMEM along the tiles: one partial a
batch row, summed outside.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from pytorch_distributed_train_tpu.ops import kda

SCOPE = "kda_inputs"  # jax.named_scope round the shaping, on both paths
# Tokens and heads a grid step, read through the hybrid cell's whole step
# (PERF.md section 6, PR 38)
KERNEL_TILE = 256
KERNEL_HEADS = 4
_EPS = 1e-6
_HALO = 8       # rows kept of a neighbouring tile: a float32 sublane tile
_PREV = 16      # rows of the block that holds them: a bfloat16 sublane tile
_SMALL = 16     # rows of the small gradients' block: 3 K taps, dt_bias, A
_F32 = jnp.float32
_VMEM_LIMIT = 64 << 20


class Plan(NamedTuple):
    tile: int       # tokens a grid step: a multiple of 16 that divides S
    heads: int      # heads a grid step
    interpret: bool


# ------------------------------------------------------------------ XLA path

def causal_short_conv(x, weight):
    """Depthwise causal convolution along the sequence: x (B, S, H, d),
    weight (K, H, d); y_t = sum_j weight[j] x_{t-(K-1-j)}, zeros before the
    start (weight[K-1] meets the current token)."""
    K, S = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0), (0, 0)))
    return sum(padded[:, j:j + S] * weight[j] for j in range(K))


@functools.partial(jax.checkpoint, static_argnums=(7,))
def shape_inputs_xla(yq, yk, yv, a, taps, a_log, dt_bias, lower_bound):
    """The module docstring's mathematics in ``jax.numpy``; elementwise
    chains: recomputed, not kept. Returns q, k, v in ``yq``'s dtype and g
    float32."""
    d = yq.shape[-1]

    def conv_silu(y, w):
        return nn.silu(causal_short_conv(y.astype(_F32), w.astype(_F32)))

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + _EPS)

    q = unit(conv_silu(yq, taps[0])) * d ** -0.5
    k = unit(conv_silu(yk, taps[1]))
    v = conv_silu(yv, taps[2])
    if lower_bound is None:
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            a.astype(_F32) + dt_bias)
    else:
        g = lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * (a.astype(_F32) + dt_bias))
    return q.astype(yq.dtype), k.astype(yq.dtype), v.astype(yq.dtype), g


# ------------------------------------------------------------- a head's tile

def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _roll(x, shift):
    """Rows rotated down by ``shift`` (static): out[t] = x[t - shift]."""
    return pltpu.roll(x, shift % x.shape[0], 0)


def _shift_down(x, before, s):
    """out[t] = x[t - s]; the rows before the tile are the LAST s of
    ``before`` (_HALO, d)."""
    if s == 0:
        return x
    rolled = _roll(x, s)
    head = jnp.where(_iota((_HALO, 1), 0) < s, _roll(before, s),
                     rolled[:_HALO])
    return jnp.concatenate([head, rolled[_HALO:]], 0)


def _shift_up(x, after, s):
    """out[t] = x[t + s]; the rows after the tile are the FIRST s of
    ``after`` (_HALO, d)."""
    if s == 0:
        return x
    T = x.shape[0]
    rolled = _roll(x, -s)
    tail = jnp.where(_iota((_HALO, 1), 0) >= _HALO - s, _roll(after, -s),
                     rolled[T - _HALO:])
    return jnp.concatenate([rolled[:T - _HALO], tail], 0)


def _conv(shifted, w):
    """sum_j w[j] x_{t-(K-1-j)} over the tile; ``shifted[j]`` is x moved
    down K-1-j rows, ``w`` (K, d). Summed in the XLA path's order."""
    y = shifted[0] * w[0:1]
    for j in range(1, len(shifted)):
        y = y + shifted[j] * w[j:j + 1]
    return y


def _unit(s):
    """rsqrt(sum_d s^2 + eps) a row: (T, 1)."""
    return jax.lax.rsqrt(jnp.sum(s * s, axis=1, keepdims=True) + _EPS)


def _softplus(z):
    """log(1 + exp z) as ``jax.nn.softplus`` computes it."""
    return jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))


def _gate(a, gate, lower_bound):
    """The log-decay g a channel; ``gate`` rows: A = exp(A_log) a channel,
    dt_bias. A bounded gate: lower_bound sigmoid(A (a + dt_bias)); None:
    -A softplus(a + dt_bias)."""
    shifted = a + gate[1:2]
    if lower_bound is None:
        return -gate[0:1] * _softplus(shifted)
    return lower_bound * jax.nn.sigmoid(gate[0:1] * shifted)


def _gate_bwd(a, gate, lower_bound, dg):
    """(da, dA a channel before the sum over rows) from g's cotangent."""
    shifted = a + gate[1:2]
    if lower_bound is None:
        return dg * (-jax.nn.sigmoid(shifted)) * gate[0:1], \
            dg * (-_softplus(shifted))
    sig = jax.nn.sigmoid(gate[0:1] * shifted)
    dz = dg * (lower_bound * sig * (1.0 - sig))
    return dz * gate[0:1], dz * shifted


# ================================================================= forward

def _fwd_kernel(yq_ref, yk_ref, yv_ref, a_ref, taps_ref, gate_ref,
                q_ref, k_ref, v_ref, g_ref, tail_ref,
                *, plan: Plan, d, K, lower_bound):
    """Grid (batch, head groups, tiles), tiles in order: ``tail_ref``
    (3, _HALO, heads d) holds each stream's last rows of the tile before."""

    @pl.when(pl.program_id(2) == 0)
    def _start():
        tail_ref[...] = jnp.zeros_like(tail_ref)

    T = yq_ref.shape[0]
    streams = ((yq_ref, q_ref, d ** -0.5), (yk_ref, k_ref, 1.0),
               (yv_ref, v_ref, None))
    for h in range(plan.heads):
        sl = slice(h * d, (h + 1) * d)
        for n, (x_ref, o_ref, scale) in enumerate(streams):
            x = x_ref[:, sl].astype(_F32)
            before = tail_ref[n, :, sl]
            tail_ref[n, :, sl] = x[T - _HALO:]
            w = taps_ref[n, :, sl]
            s = nn.silu(_conv(
                [_shift_down(x, before, K - 1 - j) for j in range(K)], w))
            if scale is not None:
                s = s * (_unit(s) * scale)
            o_ref[:, sl] = s.astype(o_ref.dtype)
        g_ref[:, sl] = _gate(a_ref[:, sl], gate_ref[:, sl], lower_bound)


def _wide(plan: Plan, d, tile_index):
    """(tile, heads d) blocks of a (B, S, H d) operand on the grid (batch,
    head group, tile) whose tile index ``tile_index`` maps."""
    return pl.BlockSpec((None, plan.tile, plan.heads * d),
                        lambda b, h, i: (b, tile_index(i), h))


def _small_specs(plan: Plan, d, K):
    w = plan.heads * d
    return (pl.BlockSpec((3, K, w), lambda b, h, i: (0, 0, h)),
            pl.BlockSpec((2, w), lambda b, h, i: (0, h)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _flat_small(taps, a_log, dt_bias):
    """taps (3, K, H, d) -> (3, K, H d) float32; exp(A_log) a channel over
    dt_bias: (2, H d)."""
    H, d = dt_bias.shape
    gate = jnp.stack([jnp.repeat(jnp.exp(a_log.astype(_F32)), d),
                      dt_bias.astype(_F32).reshape(H * d)])
    return taps.astype(_F32).reshape(*taps.shape[:2], H * d), gate


# ``jit(inline=True)`` round a launch: the launch's jaxpr, kernel body and
# all, is traced once a shape and process and replayed at every call site
# under that site's own scopes (a ``pallas_call`` traces its kernel anew at
# every call, and a step calls each of the pair five to ten times, the
# trainer's two traces of the model ten more: PERF.md section 6, PR 38).
@functools.partial(jax.jit, static_argnums=(7, 8), inline=True)
def _fwd(yq, yk, yv, a, taps, a_log, dt_bias, lower_bound, plan: Plan):
    B, S, H, d = yq.shape
    K = taps.shape[1]
    wide = _wide(plan, d, lambda i: i)
    flat = lambda x: x.reshape(B, S, H * d)  # noqa: E731
    like = lambda dtype: jax.ShapeDtypeStruct((B, S, H * d), dtype)  # noqa: E731
    q, k, v, g = pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, d=d, K=K,
                          lower_bound=lower_bound),
        grid=(B, H // plan.heads, S // plan.tile),
        in_specs=[wide] * 4 + list(_small_specs(plan, d, K)),
        out_specs=[wide] * 4,
        out_shape=[like(yq.dtype)] * 3 + [like(_F32)],
        scratch_shapes=[pltpu.VMEM((3, _HALO, plan.heads * d), _F32)],
        compiler_params=_params(),
        name="kda_inputs_fwd",
        interpret=plan.interpret,
    )(flat(yq), flat(yk), flat(yv), flat(a),
      *_flat_small(taps, a_log, dt_bias))
    return tuple(x.reshape(B, S, H, d) for x in (q, k, v, g))


# ================================================================ backward

def _bwd_kernel(yq_ref, yk_ref, yv_ref, a_ref, pq_ref, pk_ref, pv_ref,
                taps_ref, gate_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dyq_ref, dyk_ref, dyv_ref, da_ref, small_ref, head_ref,
                *, plan: Plan, d, K, lower_bound):
    """The forward's grid with the tiles walked from the last to the first:
    ``head_ref`` (3, _HALO, heads d) holds each stream's first rows of dy
    of the tile after; ``small_ref`` (_SMALL, heads d) the sums over the
    tiles so far: rows n K + j the taps', 3 K dt_bias', 3 K + 1 A's."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _start():
        head_ref[...] = jnp.zeros_like(head_ref)
        small_ref[...] = jnp.zeros_like(small_ref)

    T = yq_ref.shape[0]
    first = i == pl.num_programs(2) - 1  # the sequence's first tile

    def add(row, x):
        small_ref[row:row + 1, sl] += jnp.sum(x, axis=0, keepdims=True)

    streams = ((yq_ref, pq_ref, dq_ref, dyq_ref, d ** -0.5),
               (yk_ref, pk_ref, dk_ref, dyk_ref, 1.0),
               (yv_ref, pv_ref, dv_ref, dyv_ref, None))
    for h in range(plan.heads):
        sl = slice(h * d, (h + 1) * d)
        for n, (x_ref, p_ref, do_ref, dx_ref, scale) in enumerate(streams):
            x = x_ref[:, sl].astype(_F32)
            before = jnp.where(
                first, 0.0, p_ref[_PREV - _HALO:, sl].astype(_F32))
            w = taps_ref[n, :, sl]
            shifted = [_shift_down(x, before, K - 1 - j) for j in range(K)]
            y = _conv(shifted, w)
            sig = jax.nn.sigmoid(y)
            ds = do_ref[:, sl].astype(_F32)
            if scale is not None:  # n = s r scale, r = rsqrt(sum s^2 + eps)
                s = y * sig
                r = _unit(s)
                along = jnp.sum(ds * s, axis=1, keepdims=True)
                ds = (ds - s * (r * r * along)) * (r * scale)
            dy = ds * (sig * (1.0 + y * (1.0 - sig)))
            for j in range(K):
                add(n * K + j, dy * shifted[j])
            after = head_ref[n, :, sl]
            head_ref[n, :, sl] = dy[:_HALO]
            dx = _conv([_shift_up(dy, after, K - 1 - j) for j in range(K)],
                       w)
            dx_ref[:, sl] = dx.astype(dx_ref.dtype)
        da, d_gate = _gate_bwd(a_ref[:, sl], gate_ref[:, sl], lower_bound,
                               dg_ref[:, sl])
        da_ref[:, sl] = da
        add(3 * K, da)
        add(3 * K + 1, d_gate)


@functools.partial(jax.jit, static_argnums=(8, 9), inline=True)
def _bwd(yq, yk, yv, a, taps, a_log, dt_bias, cts, lower_bound, plan: Plan):
    B, S, H, d = yq.shape
    K = taps.shape[1]
    tiles, w = S // plan.tile, plan.heads * d
    back = lambda i: tiles - 1 - i  # noqa: E731
    wide = _wide(plan, d, back)
    # the _PREV rows that end where the tile starts (the first tile's: any)
    prev = pl.BlockSpec(
        (None, _PREV, w), lambda b, h, i: (
            b, jnp.maximum(back(i) * (plan.tile // _PREV) - 1, 0), h))
    small = pl.BlockSpec((None, _SMALL, w), lambda b, h, i: (b, 0, h))
    flat = lambda x: x.reshape(B, S, H * d)  # noqa: E731
    like = lambda dtype: jax.ShapeDtypeStruct((B, S, H * d), dtype)  # noqa: E731
    ys = flat(yq), flat(yk), flat(yv)
    flat_taps, gate = _flat_small(taps, a_log, dt_bias)
    dyq, dyk, dyv, da, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan, d=d, K=K,
                          lower_bound=lower_bound),
        grid=(B, H // plan.heads, tiles),
        in_specs=[wide] * 4 + [prev] * 3 + list(_small_specs(plan, d, K))
        + [wide] * 4,
        out_specs=[wide] * 4 + [small],
        out_shape=[like(yq.dtype)] * 3 + [like(_F32)]
        + [jax.ShapeDtypeStruct((B, _SMALL, H * d), _F32)],
        scratch_shapes=[pltpu.VMEM((3, _HALO, w), _F32)],
        compiler_params=_params(),
        name="kda_inputs_bwd",
        interpret=plan.interpret,
    )(*ys, flat(a), *ys, flat_taps, gate, *(flat(x) for x in cts))
    sums = jnp.sum(sums, axis=0).reshape(_SMALL, H, d)
    dtaps = sums[:3 * K].reshape(3, K, H, d)
    # A = exp(A_log) a head: dA_log = A sum_d dA
    da_log = jnp.exp(a_log.astype(_F32)) * jnp.sum(sums[3 * K + 1], axis=-1)
    shape = lambda x: x.reshape(B, S, H, d)  # noqa: E731
    return (shape(dyq), shape(dyk), shape(dyv), shape(da), dtaps, da_log,
            sums[3 * K])


# ============================================================== entry point

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _shaped(yq, yk, yv, a, taps, a_log, dt_bias, lower_bound, plan):
    return _fwd(yq, yk, yv, a, taps, a_log, dt_bias, lower_bound, plan)


def _shaped_fwd(yq, yk, yv, a, taps, a_log, dt_bias, lower_bound, plan):
    out = _fwd(yq, yk, yv, a, taps, a_log, dt_bias, lower_bound, plan)
    return out, (yq, yk, yv, a, taps, a_log, dt_bias)


def _shaped_bwd(lower_bound, plan, res, cts):
    yq, yk, yv, a, taps, a_log, dt_bias = res
    dq, dk, dv, dg = cts
    dyq, dyk, dyv, da, dtaps, da_log, ddt = _bwd(
        yq, yk, yv, a, taps, a_log, dt_bias,
        (dq.astype(yq.dtype), dk.astype(yq.dtype), dv.astype(yq.dtype),
         dg.astype(_F32)), lower_bound, plan)
    return (dyq, dyk, dyv, da.astype(a.dtype), dtaps.astype(taps.dtype),
            da_log.astype(a_log.dtype), ddt.astype(dt_bias.dtype))


_shaped.defvjp(_shaped_fwd, _shaped_bwd)


def kda_inputs(yq, yk, yv, a, taps, a_log, dt_bias, *, lower_bound,
               plan: Plan):
    """The kernel pair. yq, yk, yv: (B, S, H, d) in one dtype, bfloat16 or
    float32; a: (B, S, H, d) float32; taps: (3, K, H, d) (q's, k's, v's);
    a_log: (H,); dt_bias: (H, d); ``lower_bound`` the gate's form (a
    number, or None: softplus); S whole tiles of ``plan.tile``, d a
    multiple of 128, H whole head groups. Returns q, k, v in yq's dtype
    and g float32."""
    K = taps.shape[1]
    if not 1 <= K - 1 <= _HALO or 3 * K + 2 > _SMALL:
        raise ValueError(f"kda_inputs: a convolution of {K} taps")
    return _shaped(yq, yk, yv, a.astype(_F32), taps, a_log, dt_bias,
                   lower_bound, plan)


def shape_inputs(yq, yk, yv, a, taps, a_log, dt_bias, *, lower_bound,
                 cp=None):
    """What ``KDAMixer`` calls: the kernel pair where ``kda.unsupported``
    lets the core's kernels run (one decision for both), else
    :func:`shape_inputs_xla`. ``taps``: the three (K, H, d) arrays; ``cp``
    the mesh's axes or None, and ``lower_bound`` the gate's form (None:
    unbounded), as :func:`kda.kda_chunked` takes them."""
    B, S, H, d = yq.shape
    lower_bound = None if lower_bound is None else float(lower_bound)
    why = kda.unsupported(S, d, d, yq.dtype, cp)
    if why is not None:
        kda.log_plan(S, None, H, d, d, f"inputs=xla reason={why}")
        with jax.named_scope(SCOPE):
            return shape_inputs_xla(yq, yk, yv, a, tuple(taps), a_log,
                                    dt_bias, lower_bound)
    taps = jnp.stack(taps)

    def local(yq, yk, yv, a, taps, a_log, dt_bias):
        S, H = yq.shape[1:3]
        tile = next(t for t in (KERNEL_TILE, 128) if S % t == 0)
        hb = next(n for n in range(KERNEL_HEADS, 0, -1) if H % n == 0)
        kda.log_plan(S, None, H, d, d,
                     f"inputs=pallas tile={tile} heads_per_step={hb}")
        with jax.named_scope(SCOPE):
            return kda_inputs(yq, yk, yv, a, taps, a_log, dt_bias,
                              lower_bound=lower_bound,
                              plan=Plan(tile, hb, kda._interpret()))

    return kda.on_own_block(
        local, cp, yq, lambda spec: (
            (spec,) * 4 + (P(None, None, spec[2]), P(spec[2]), P(spec[2])),
            (spec,) * 4))(yq, yk, yv, a, taps, a_log, dt_bias)
