"""Pallas TPU kernels for the gated delta rule's chunk core (ops/kda.py): a
chunk's tables live and die in VMEM.

Left to XLA the core is a ``lax.scan`` of some sixty small fusions an
iteration forward and two hundred backward, each over 1/64 of a layer's
tokens, each writing its float32 intermediate to HBM for the next to read
(PERF.md section 5). Here a grid step takes ``TILE`` tokens of a few heads:
it forms the tokens' tables (the cumulative decay, A, B, the Neumann
inverse, W, U0, the end-of-chunk keys: ops/kda.py's module docstring) from
the q/k/v/g/beta tiles, walks the tile's chunks through the three products
with the state, and writes o. The state is a float32 VMEM scratch carried
along the sequential tile axis; HBM sees q, k, v, g, beta in and o out,
plus one float32 state a tile and head (the state a tile STARTS from),
which the backward kernel re-walks its tile from. The backward kernel
recomputes the tile's tables, walks the chunks forward to recover each
chunk's entry state, then walks them back with dS in a VMEM scratch.

What makes it fast (read on a v5e at (2, 8192, 32, 128); PERF.md section
6, PR 31). A head's tile is a CHAIN of dependent products, each a fraction
of the MXUs' pipeline, so a grid step carries several heads and traces
them in step (``_lockstep``): 13.1 ms a layer forward head after head, 11.7
in step. The inverse is over half of what is left: its stage i needs
out (I + a^(2^i)) = out + out p and p p, which share their right operand,
so one product of the stacked rows [out; p] does both (11.7 -> 7.9 ms).

Numbers are ops/kda.py's, rounding point for rounding point: float32 g,
sums of g and state; the decay factors by the gate's form (``Plan.bounded``;
ops/kda.py's module docstring): a bounded gate's blocks against their
mid-block reference point, one product a block; an unbounded gate's against
the block's FIRST row for the pairs of different blocks (every factor <= 1)
and, for a block's own pairs, G_t - G_j by subtraction on the VPU, one
offset t - j at a time over the whole tile (``_own_pairs``: rows rolled
down by the offset meet their keys; 16 offsets, no product);
operands of the tables' and the state's products in the callers' dtype
with float32 accumulation; the inverse and the products with it at three
bfloat16 passes over operands split into a high and a low part by hand
(Mosaic has one pass or full float32, nothing between), and full float32
(``HIGHEST``) everywhere when the operands themselves are float32. The sums
of g are products with a 0/1 triangle: g split into three bfloat16 parts,
each product exact, accumulated in float32.

The hand-derived gradient (one chunk; ``St`` the state TRANSPOSED,
(d_v, d_k), so that a decay scales lanes; ``~`` rounds to the operands'
dtype; dS' the cotangent of the chunk's exit state):

    dQg = dO~ Sd          dB = tril(dO~ u~^T)      du = B~^T dO~ + Kend~ dS'~^T
    dKend = u~ dS'~       dgend = colsum(dS' * St)  dW = -du~ Sd
    dSt = dS' gend - du~^T W~ + dO~^T Qg~
    [rk | rv] = inv^T [dW | du]                     dA = -strict([rk | rv] [W | U0]^T)

then the elementwise factors, the tables' two products a block of 16 rows,
and ``dg`` = the reverse in-chunk sum of dG.

Layout: (B, S, H, d) arrives as (B, S, H d), which costs nothing; a block
is ``(TILE, heads_per_step d)`` lanes of it. beta travels as
(B, H / heads_per_step, S, heads_per_step), a few MB.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_train_tpu.ops.flash_attention import _NN, _NT, _TN
from pytorch_distributed_train_tpu.ops.kda import _MASKED, BLOCK

TILE = 128      # tokens a grid step and head: one MXU tile of rows
_F32 = jnp.float32
_BF16 = jnp.bfloat16
_VMEM_LIMIT = 64 << 20


class Plan(NamedTuple):
    chunk: int      # tokens a chunk: a power of two, 32 <= chunk <= TILE
    heads: int      # heads a grid step
    interpret: bool
    bounded: bool = True   # the decay gate's form (module docstring)


# ------------------------------------------------------------ small products

def _mm(a, b, dims, exact):
    """One product in the operands' dtype, float32 accumulation; float32
    operands (``exact``) take the full-precision product."""
    if exact:
        return jax.lax.dot_general(a.astype(_F32), b.astype(_F32), dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=_F32)
    return jax.lax.dot_general(a.astype(_BF16), b.astype(_BF16), dims,
                               preferred_element_type=_F32)


def _split(x):
    """float32 -> (high, low) bfloat16 parts."""
    hi = x.astype(_BF16)
    return hi, (x - hi.astype(_F32)).astype(_BF16)


def _mm3(a, b, dims, exact):
    """``Precision.HIGH`` by hand: three bfloat16 passes over float32
    operands (the low x low term is dropped). ``a`` and ``b`` may come
    already split."""
    if exact:
        return _mm(a, b, dims, True)
    ah, al = a if isinstance(a, tuple) else _split(a)
    bh, bl = b if isinstance(b, tuple) else _split(b)
    dot = lambda x, y: jax.lax.dot_general(  # noqa: E731
        x, y, dims, preferred_element_type=_F32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _tri_sum(tri, x, dims):
    """Product of a 0/1 matrix (exact in bfloat16) with float32 ``x``
    split three ways: every partial product exact, the sum float32."""
    hi, lo = _split(x)
    lo2 = (x - hi.astype(_F32) - lo.astype(_F32)).astype(_BF16)
    dot = lambda y: jax.lax.dot_general(  # noqa: E731
        tri, y, dims, preferred_element_type=_F32)
    return dot(hi) + (dot(lo) + dot(lo2))


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _lockstep(heads):
    """Run the heads' computations (generators that yield where the next
    product needs the last one's result) a stage at a time, round robin.
    One head's tile is a chain of some fifteen dependent products: traced
    head after head the chains run end to end; traced in step, a head's
    products fill the others' waits (module docstring). Returns what each
    returned, in order."""
    heads = list(heads)
    done = [None] * len(heads)
    live = list(range(len(heads)))
    while live:
        for i in list(live):
            try:
                next(heads[i])
            except StopIteration as stop:
                done[i] = stop.value
                live.remove(i)
    return done


class _Masks(NamedTuple):
    """A tile's (T, T) masks, the same for every head of a grid step."""

    eye: jax.Array      # float32 identity
    lower: jax.Array    # bool: same chunk, column <= row
    strict: jax.Array   # bool: same chunk, column < row
    tri: jax.Array      # ``lower`` as bfloat16 0/1: the in-chunk sums


def _masks(T, chunk) -> _Masks:
    shift = chunk.bit_length() - 1
    r, c = _iota((T, T), 0), _iota((T, T), 1)
    same = (r >> shift) == (c >> shift)
    lower = same & (c <= r)
    return _Masks((r == c).astype(_F32), lower, same & (c < r),
                  lower.astype(_BF16))


def _unit_lower_inverse(a, eye, chunk, exact):
    """(I + a)^-1 for ``a`` strictly lower triangular inside diagonal
    blocks of ``chunk``: the finite Neumann product of ops/kda.py,
    (I - a)(I + a^2)(I + a^4)... A generator: one stage a factor."""
    T = a.shape[0]
    out, power = eye - a, a
    n = chunk.bit_length() - 2  # log2(chunk) - 1 factors after (I - a)
    for i in range(n + 1):
        # stage i: out (I + a^(2^i)) = out + out p and, for the next stage,
        # p p share their right operand p: one product of the stacked rows
        p = power if exact else _split(power)
        if i == 0:
            power = _mm3(p, p, _NN, exact)
        elif i == n:
            out = out + _mm3(out, p, _NN, exact)
        else:
            both = _mm3(jnp.concatenate([out, power], 0), p, _NN, exact)
            out, power = out + both[:T], both[T:]
        yield
    return out


# ------------------------------------------------------------ a tile's tables

class _Tables(NamedTuple):
    tk: jax.Array       # (T, T) strictly lower in-chunk K K^T table, no beta
    bmat: jax.Array     # (T, T) B
    inv: jax.Array      # (T, T) (I + A)^-1
    gamma: jax.Array    # (T, dk) exp(G)
    solw: jax.Array     # (T, dk) W before rounding
    u0: jax.Array       # (T, dv)
    ke: jax.Array       # (T, dk) exp(G_C - G)
    gend: list          # a chunk: (1, dk) exp(G_C)
    blocks: list        # a block of 16 rows: its decayed rows [q; k] and
    #                     keys as the tables' product took them, and the
    #                     two decay factors (the backward kernel's)
    G: jax.Array        # (T, dk) the in-chunk sums of g


def _block_decays(G, i, chunk, bounded=True):
    """Row block i's decay factors: its rows against the block's reference
    point, and every key of its causal reach against the same point. A
    bounded gate: the sum at the block's middle, the reach up to the
    block's end. An unbounded one: the sum at its first row and the keys
    BEFORE the block, both factors <= 1."""
    T = G.shape[0]
    lo, hi = i * BLOCK, (i + 1) * BLOCK
    at = lo + BLOCK // 2 - 1 if bounded else lo
    ref = G[at:at + 1, :]
    rowdec = jnp.exp(G[lo:hi, :] - ref)
    j = _iota((T, 1), 0)
    reach = (j >= (lo // chunk) * chunk) & (j < (hi if bounded else lo))
    keydec = jnp.exp(jnp.where(reach, ref - G, _MASKED))
    return rowdec, keydec


def _roll(x, shift):
    """Rows rotated down by ``shift`` (static): out[t] = x[t - shift]."""
    return pltpu.roll(x, shift % x.shape[0], 0)


def _own_pair_factors(k, G, s):
    """Offset s inside a block of 16 rows: row t meets key t - s where both
    lie in one block (``live``). Returns (the key's row rolled to its
    query's, exp(G_t - G_{t-s}) with 0 where not live: the difference is
    taken BEFORE the exponential, and is <= 0)."""
    if s == 0:
        return k, None
    live = (_iota((k.shape[0], 1), 0) & (BLOCK - 1)) >= s
    return _roll(k, s), jnp.exp(jnp.where(live, G - _roll(G, s), _MASKED))


def _on_offset(col, s):
    """(T, T) bool: column = row - s; ``col`` = column - row."""
    return col == -s


def _own_pairs(q, k, G):
    """An unbounded gate's in-block pairs of a tile: (tq, tk) (T, T)
    float32, tq[t, j] = sum_c q_tc k_jc exp(G_tc - G_jc) for j <= t of t's
    block, tk the same of k's rows for j < t, zeros elsewhere."""
    T = q.shape[0]
    col = _iota((T, T), 1) - _iota((T, T), 0)
    tq = tk = jnp.zeros((T, T), _F32)
    for s in range(BLOCK):
        ks, e = _own_pair_factors(k, G, s)
        w = ks if e is None else ks * e
        tq = jnp.where(_on_offset(col, s),
                       jnp.sum(q * w, axis=1, keepdims=True), tq)
        if s:
            tk = jnp.where(_on_offset(col, s),
                           jnp.sum(k * w, axis=1, keepdims=True), tk)
    return tq, tk


def _own_pairs_bwd(q, k, G, dtq, dtk):
    """The cotangents of q, k and G through :func:`_own_pairs`, from the
    tables' (T, T) cotangents (already masked to the kept pairs)."""
    T = q.shape[0]
    col = _iota((T, T), 1) - _iota((T, T), 0)
    dq, dk, dG = (jnp.zeros_like(q) for _ in range(3))
    for s in range(BLOCK):
        ks, e = _own_pair_factors(k, G, s)
        pick = lambda d: jnp.sum(  # noqa: E731
            jnp.where(_on_offset(col, s), d, 0.0), axis=1, keepdims=True)
        cq = pick(dtq)
        if s == 0:  # the diagonal: exp(0), q's table alone
            dq, dk = dq + cq * k, dk + cq * q
            continue
        ck = pick(dtk)
        w = ks * e
        dq, dk = dq + cq * w, dk + ck * w
        mix = cq * q + ck * k
        # the key t - s: its cotangent goes s rows up (0 where not live)
        dk = dk + _roll(mix * e, -s)
        dge = mix * w
        dG = dG + dge - _roll(dge, -s)
    return dq, dk, dG


def _tables(q, k, v, g, beta, m: _Masks, *, chunk, dtype, exact,
            bounded=True):
    """q, k, g: (T, dk) float32; v: (T, dv) float32; beta: (T, 1). A
    generator (``_lockstep``) that returns the tile's ``_Tables``."""
    T = q.shape[0]
    G = _tri_sum(m.tri, g, _NN)
    yield
    tq, tk, blocks = [], [], []
    for i in range(T // BLOCK):
        rowdec, keydec = _block_decays(G, i, chunk, bounded)
        lo, hi = i * BLOCK, (i + 1) * BLOCK
        rows = jnp.concatenate(
            [q[lo:hi] * rowdec, k[lo:hi] * rowdec], 0).astype(dtype)
        keys = (k * keydec).astype(dtype)
        if bounded or lo % chunk:
            t = _mm(rows, keys, _NT, exact)
        else:  # a chunk's first block: no key before it
            t = jnp.zeros((2 * BLOCK, T), _F32)
        tq.append(t[:BLOCK])
        tk.append(t[BLOCK:])
        blocks.append((rows, keys, rowdec, keydec))
    tq, tk = jnp.concatenate(tq, 0), jnp.concatenate(tk, 0)
    if not bounded:
        yield
        own_q, own_k = _own_pairs(q, k, G)
        tq, tk = tq + own_q, tk + own_k
    bmat = jnp.where(m.lower, tq, 0.0)
    tk = jnp.where(m.strict, tk, 0.0)
    yield
    inv = yield from _unit_lower_inverse(tk * beta, m.eye, chunk, exact)
    gamma = jnp.exp(G)
    inv_parts = inv if exact else _split(inv)
    solw = _mm3(inv_parts, k * gamma * beta, _NN, exact)
    u0 = _mm3(inv_parts, v * beta, _NN, exact)
    yield
    ends = [G[(n + 1) * chunk - 1:(n + 1) * chunk, :]
            for n in range(T // chunk)]
    g_end = jnp.concatenate(
        [jnp.broadcast_to(e, (chunk, e.shape[1])) for e in ends], 0)
    return _Tables(tk, bmat, inv, gamma, solw, u0, jnp.exp(g_end - G),
                   [jnp.exp(e) for e in ends], blocks, G)


def _walk(tb: _Tables, qg, kend, st, *, chunk, dtype, exact):
    """The tile's chunks in order from the transposed state ``st``
    (dv, dk) float32. A generator that returns each chunk's entry state,
    u (T, dv), the state's share of o (T, dv) and the exit state."""
    w = tb.solw.astype(dtype)
    entries, us, os_ = [], [], []
    for n in range(qg.shape[0] // chunk):
        sl = slice(n * chunk, (n + 1) * chunk)
        sd = st.astype(dtype)
        entries.append(st)
        u = tb.u0[sl] - _mm(w[sl], sd, _NT, exact)
        os_.append(_mm(qg[sl], sd, _NT, exact))
        yield
        st = st * tb.gend[n] + _mm(u.astype(dtype), kend[sl], _TN, exact)
        us.append(u)
        yield
    return entries, jnp.concatenate(us, 0), jnp.concatenate(os_, 0), st


# ================================================================= forward

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref, st_ref,
                *, plan: Plan, dk, dv):
    """Grid (batch, head groups, tiles), tiles sequential: ``st_ref`` holds
    the group's transposed states across the tiles."""
    dtype = q_ref.dtype
    exact = dtype == _F32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    masks = _masks(q_ref.shape[0], plan.chunk)

    def head(h):
        kl, vl = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        q, k = q_ref[:, kl].astype(_F32), k_ref[:, kl].astype(_F32)
        v = v_ref[:, vl].astype(_F32)
        tb = yield from _tables(q, k, v, g_ref[:, kl], beta_ref[:, h:h + 1],
                                masks, chunk=plan.chunk, dtype=dtype,
                                exact=exact, bounded=plan.bounded)
        entries, u, o, st = yield from _walk(
            tb, (q * tb.gamma).astype(dtype), (k * tb.ke).astype(dtype),
            st_ref[h], chunk=plan.chunk, dtype=dtype, exact=exact)
        o = o + _mm(tb.bmat.astype(dtype), u.astype(dtype), _NN, exact)
        return entries[0], st, o

    for h, (st0, st, o) in enumerate(_lockstep(map(head, range(plan.heads)))):
        s_ref[h], st_ref[h] = st0, st
        o_ref[:, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)


def _specs(plan: Plan, dk, dv, tile_index):
    """Block specs of q/k/g, v, beta and a state a tile, for a grid of
    (batch, head group, tile) whose tile index ``tile_index`` maps."""
    hb = plan.heads
    wide = lambda d: pl.BlockSpec(  # noqa: E731
        (None, TILE, hb * d), lambda b, h, i: (b, tile_index(i), h))
    beta = pl.BlockSpec((None, None, TILE, hb),
                        lambda b, h, i: (b, h, tile_index(i), 0))
    state = pl.BlockSpec((None, None, hb, dv, dk),
                         lambda b, h, i: (b, tile_index(i), h, 0, 0))
    return wide(dk), wide(dv), beta, state


def _group_beta(beta, hb):
    """(B, S, H) -> (B, H / hb, S, hb)."""
    B, S, H = beta.shape
    return jnp.swapaxes(beta.astype(_F32).reshape(B, S, H // hb, hb), 1, 2)


def _fwd(q, k, v, g, beta, plan: Plan):
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    hb = plan.heads
    kspec, vspec, bspec, sspec = _specs(plan, dk, dv, lambda i: i)
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, dk=dk, dv=dv),
        grid=(B, H // hb, S // TILE),
        in_specs=[kspec, kspec, vspec, kspec, bspec],
        out_specs=[vspec, sspec],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * dv), q.dtype),
            jax.ShapeDtypeStruct((B, S // TILE, H, dv, dk), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="kda_fwd",
        interpret=plan.interpret,
    )(q.reshape(B, S, H * dk), k.reshape(B, S, H * dk),
      v.reshape(B, S, H * dv), g.reshape(B, S, H * dk),
      _group_beta(beta, hb))
    return o.reshape(B, S, H, dv), states


# ================================================================ backward

def _bwd_head(q, k, v, g, beta, do, st, dst, m: _Masks, *, chunk, dtype,
              exact, bounded=True):
    """One head's tile: the cotangents of q, k, v, g (T, d), of beta
    (T, 1), and of the tile's entry state, from ``do`` (T, dv), the entry
    state ``st`` and the exit state's cotangent ``dst`` (dv, dk). A
    generator (``_lockstep``)."""
    T = q.shape[0]
    tb = yield from _tables(q, k, v, g, beta, m, chunk=chunk, dtype=dtype,
                            exact=exact, bounded=bounded)
    qg, kend = (q * tb.gamma).astype(dtype), (k * tb.ke).astype(dtype)
    entries, u, _, _ = yield from _walk(tb, qg, kend, st, chunk=chunk,
                                        dtype=dtype, exact=exact)
    w, ub, dob = tb.solw.astype(dtype), u.astype(dtype), do.astype(dtype)
    du_b = _mm(tb.bmat.astype(dtype), dob, _TN, exact)
    dbm = jnp.where(m.lower, _mm(dob, ub, _NT, exact), 0.0)
    yield

    n_chunks = T // chunk
    dw, du, dqg, dkend, dgc = ([None] * n_chunks for _ in range(5))
    for n in reversed(range(n_chunks)):
        sl = slice(n * chunk, (n + 1) * chunk)
        sd, dsd = entries[n].astype(dtype), dst.astype(dtype)
        dkend[n] = _mm(ub[sl], dsd, _NN, exact)
        du[n] = du_b[sl] + _mm(kend[sl], dsd, _NT, exact)
        # exp(G_C)'s share of dG at the chunk's last row
        dgc[n] = jnp.sum(dst * entries[n], axis=0, keepdims=True) \
            * tb.gend[n]
        yield
        dun = du[n].astype(dtype)
        dw[n] = -_mm(dun, sd, _NN, exact)
        dqg[n] = _mm(dob[sl], sd, _NN, exact)
        dst = dst * tb.gend[n] + (_mm(dob[sl], qg[sl], _TN, exact)
                                  - _mm(dun, w[sl], _TN, exact))
        yield
    dw, du, dqg, dkend = (jnp.concatenate(x, 0) for x in (dw, du, dqg, dkend))

    inv = tb.inv if exact else _split(tb.inv)
    rk, rv = _mm3(inv, dw, _TN, exact), _mm3(inv, du, _TN, exact)
    yield
    da = -jnp.where(m.strict, _mm3(rk, tb.solw, _NT, exact)
                    + _mm3(rv, tb.u0, _NT, exact), 0.0)
    yield
    kgam = k * tb.gamma
    dv_ = rv * beta
    dbeta = jnp.sum(rk * kgam, axis=1, keepdims=True) \
        + jnp.sum(rv * v, axis=1, keepdims=True) \
        + jnp.sum(da * tb.tk, axis=1, keepdims=True)
    dq = dqg * tb.gamma
    h_end = dkend * k * tb.ke
    dk_ = rk * tb.gamma * beta + dkend * tb.ke
    dG = (rk * beta * k + dqg * q) * tb.gamma - h_end
    # G_C enters through exp(G_C - G) and exp(G_C): the chunk's last row
    last = jnp.concatenate(
        [jnp.broadcast_to(
            dgc[n] + jnp.sum(h_end[n * chunk:(n + 1) * chunk], axis=0,
                             keepdims=True), (chunk, q.shape[1]))
         for n in range(n_chunks)], 0)
    row = _iota((T, 1), 0)
    dG = dG + jnp.where((row & (chunk - 1)) == chunk - 1, last, 0.0)

    # the tables' two products, a block of rows at a time. A block's
    # reference point R leaves the exact tables but not the rounded ones:
    # its cotangent (rounding noise) goes to the row R was read from, as
    # autodiff's does, so that a block's share of dG sums to zero and the
    # noise does not pile up along the chunk's reverse sum.
    dtk = da * beta
    dq_tab, dk_row, d_ref = [], [], []
    dk_key = jnp.zeros_like(k)
    for i, (rows, keys, rowdec, keydec) in enumerate(tb.blocks):
        lo, hi = i * BLOCK, (i + 1) * BLOCK
        d = jnp.concatenate([dbm[lo:hi], dtk[lo:hi]], 0).astype(dtype)
        if bounded or lo % chunk:
            drows = _mm(d, keys, _NN, exact)
            dkeys = _mm(d, rows, _TN, exact) * keydec
        else:  # a chunk's first block met no key in the product
            drows, dkeys = jnp.zeros((2 * BLOCK, q.shape[1]), _F32), 0.0
        dk_key = dk_key + dkeys
        dq_tab.append(drows[:BLOCK] * rowdec)
        dk_row.append(drows[BLOCK:] * rowdec)
        d_ref.append(jnp.broadcast_to(
            jnp.sum(k * dkeys, axis=0, keepdims=True)
            - jnp.sum(q[lo:hi] * dq_tab[i] + k[lo:hi] * dk_row[i], axis=0,
                      keepdims=True), (BLOCK, q.shape[1])))
    dq_tab, dk_row, d_ref = (jnp.concatenate(x, 0)
                             for x in (dq_tab, dk_row, d_ref))
    # the row a block's reference point was read from
    ref_row = BLOCK // 2 - 1 if bounded else 0
    dG = dG + q * dq_tab + k * (dk_row - dk_key) \
        + jnp.where((row & (BLOCK - 1)) == ref_row, d_ref, 0.0)
    yield
    if not bounded:  # the blocks' own pairs
        dq_own, dk_own, dG_own = _own_pairs_bwd(q, k, tb.G, dbm, dtk)
        dq_tab, dk_key, dG = dq_tab + dq_own, dk_key + dk_own, dG + dG_own
        yield
    dg = _tri_sum(m.tri, dG, _TN)
    return dq + dq_tab, dk_ + dk_row + dk_key, dv_, dg, dbeta, dst


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dst_ref,
                *, plan: Plan, dk, dv):
    """The forward's grid with the tiles walked from the last to the
    first: ``dst_ref`` holds the group's state cotangents."""
    dtype = q_ref.dtype
    exact = dtype == _F32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    masks = _masks(q_ref.shape[0], plan.chunk)

    def head(h):
        kl, vl = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        return _bwd_head(
            q_ref[:, kl].astype(_F32), k_ref[:, kl].astype(_F32),
            v_ref[:, vl].astype(_F32), g_ref[:, kl], beta_ref[:, h:h + 1],
            do_ref[:, vl].astype(_F32), s_ref[h], dst_ref[h], masks,
            chunk=plan.chunk, dtype=dtype, exact=exact,
            bounded=plan.bounded)

    lane = _iota(dbeta_ref.shape, 1)
    dbeta_all = jnp.zeros(dbeta_ref.shape, _F32)
    for h, (dq, dk_, dv_, dg, dbeta, dst) in enumerate(
            _lockstep(map(head, range(plan.heads)))):
        kl, vl = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        dst_ref[h] = dst
        dq_ref[:, kl] = dq.astype(dq_ref.dtype)
        dk_ref[:, kl] = dk_.astype(dk_ref.dtype)
        dv_ref[:, vl] = dv_.astype(dv_ref.dtype)
        dg_ref[:, kl] = dg
        dbeta_all = jnp.where(lane == h, dbeta, dbeta_all)
    dbeta_ref[...] = dbeta_all


def _bwd(q, k, v, g, beta, states, do, plan: Plan):
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    hb, tiles = plan.heads, S // TILE
    kspec, vspec, bspec, sspec = _specs(plan, dk, dv,
                                        lambda i: tiles - 1 - i)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan, dk=dk, dv=dv),
        grid=(B, H // hb, tiles),
        in_specs=[kspec, kspec, vspec, kspec, bspec, sspec, vspec],
        out_specs=[kspec, kspec, vspec, kspec, bspec],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * dk), q.dtype),
            jax.ShapeDtypeStruct((B, S, H * dk), k.dtype),
            jax.ShapeDtypeStruct((B, S, H * dv), v.dtype),
            jax.ShapeDtypeStruct((B, S, H * dk), _F32),
            jax.ShapeDtypeStruct((B, H // hb, S, hb), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="kda_bwd",
        interpret=plan.interpret,
    )(q.reshape(B, S, H * dk), k.reshape(B, S, H * dk),
      v.reshape(B, S, H * dv), g.reshape(B, S, H * dk),
      _group_beta(beta, hb), states, do.reshape(B, S, H * dv))
    shape = lambda x, d: x.reshape(B, S, H, d)  # noqa: E731
    return (shape(dq, dk), shape(dk_, dk), shape(dv_, dv), shape(dg, dk),
            jnp.swapaxes(dbeta, 1, 2).reshape(B, S, H))


# ============================================================== entry point

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _core(q, k, v, g, beta, plan):
    return _fwd(q, k, v, g, beta, plan)[0]


def _core_fwd(q, k, v, g, beta, plan):
    o, states = _fwd(q, k, v, g, beta, plan)
    return o, (q, k, v, g, beta, states)


def _core_bwd(plan, res, do):
    q, k, v, g, beta, states = res
    dq, dk, dv, dg, dbeta = _bwd(q, k, v, g, beta, states, do, plan)
    return dq, dk, dv, dg.astype(g.dtype), dbeta.astype(beta.dtype)


_core.defvjp(_core_fwd, _core_bwd)


def kda_pallas(q, k, v, g, beta, plan: Plan):
    """The kernel pair. q, k: (B, S, H, d_k) and v: (B, S, H, d_v) in one
    dtype, bfloat16 or float32; g: (B, S, H, d_k) float32; beta:
    (B, S, H) float32; S whole tiles, d_k and d_v multiples of 128, H whole
    head groups. Returns o (B, S, H, d_v) in q's dtype."""
    return _core(q, k, v, g.astype(_F32), beta.astype(_F32), plan)
