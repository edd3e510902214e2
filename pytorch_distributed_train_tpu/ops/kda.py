"""Gated delta rule with a per-channel decay (Kimi Delta Attention, Kimi
Linear report arXiv:2510.26692): the recurrent mixer's core, chunked.

A head keeps a state S in R^{d_k x d_v}, S_0 = 0, and for each token t with
a query q_t and key k_t in R^{d_k}, a value v_t in R^{d_v}, a log-decay
g_t in (-inf, 0]^{d_k} and a step size beta_t in [0, 2] (past 1 the factor
I - beta k k^T has a negative eigenvalue: the state may flip along k):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`kda_recurrent` is that, token by token (the tests' ground truth).
:func:`kda_chunked` computes the same in chunks of C tokens. With
u_t = beta_t (v_t - (Diag(exp g_t) S_{t-1})^T k_t) the update reads
S_t = Diag(exp g_t) S_{t-1} + k_t u_t^T, and inside a chunk that starts
from S (G_t the inclusive sum of g over the chunk's tokens up to t):

    A_tj = beta_t sum_c k_tc k_jc exp(G_tc - G_jc)      j <  t
    B_tj =        sum_c q_tc k_jc exp(G_tc - G_jc)      j <= t
    U = (I + A)^-1 (beta V  -  beta (K exp G) S)  =  U0 - W S
    O = (Q exp G) S + B U
    S' = Diag(exp G_C) S + (K exp(G_C - G))^T U

A, B, U0 and W need no state, so they are computed for all chunks at once;
only the three products with S run in sequence, one step a chunk.

Numbers. The state, g and its sums are float32. exp(G_t - G_j) is never
formed from exp(G_t) and exp(-G_j) over a whole chunk (32 x 5 overflows):
rows are taken in blocks of 16 tokens (``BLOCK``), and the decay gate's FORM
says how a block's factors are made (``lower_bound`` of
:func:`kda_chunked`; the mixer hands over its gate's bound, or None):

* bounded, g in [lower_bound, 0] (the gate ``lower_bound * sigmoid(.)``):
  each block against its own reference point R (the sum at the block's
  middle), so that queries carry exp(G_t - R) and keys exp(R - G_j), both
  within exp(+-8 |lower_bound|) inside the block and the keys' smaller
  before it. THE BOUND IS WHAT MAKES THIS PRODUCT FORM SAFE: it relies on
  ``BLOCK * |lower_bound| < 88``, and exp(+-40) times a small component
  stays a normal float32 (a reference at the block's start reached
  exp(-80), and the small components of q went denormal). Every pair of a
  chunk then comes out of one matrix product a block.
* unbounded, g in (-inf, 0] (``lower_bound=None``: the report's own gate
  ``-exp(A_log) softplus(.)``, where one token can decay a channel by
  exp(-30)): no bound to lean on, so no factor above 1 is ever formed.
  Pairs of DIFFERENT blocks take the reference at the query block's first
  row: exp(G_t - R) <= 1 and exp(R - G_j) <= 1, a product that underflows
  only where the true factor exp(G_t - G_j) does. Pairs INSIDE a block
  form G_t - G_j by subtraction before the exponential, 16 x 16 pairs a
  channel, summed over the channels in float32 (no matrix product: the
  price of the missing bound). Nothing here relies on any bound on g.

Everything else of a chunk (exp(G), exp(G_C - G), exp(G_C)) is at most 1 in
both forms. beta enters as a plain factor, so [0, 2] needs nothing more.
(I + A)^-1 is the finite Neumann product (I - A)(I + A^2)(I + A^4)...,
exact because A is strictly lower triangular. Its products and the one
with the right-hand sides take float32 operands in three bfloat16 passes
(``Precision.HIGH``, some 1e-5 of relative error, where W is rounded to
bfloat16 next and every other product of the chunk has bfloat16 operands):
at six passes (``HIGHEST``) these small batched products were two thirds
of what the core costs the TPU's compiler (17.8 s an instance against 9.8,
sandbox compile, PR 26; the step holds twenty instances).

Two paths, one algorithm, :func:`kda_recurrent` the ground truth of both.
On a TPU, at head widths that are multiples of 128 and a length of whole
tiles, :func:`kda_chunked` takes the Pallas kernel pair of
ops/kda_kernel.py (``unsupported`` says why not, from what a call can see;
no option): a tile's tables are formed and consumed in VMEM, one launch a
layer and direction. Everywhere else (the CPU tests, the rehearsals, small
heads) it is the XLA scan below. The kernels keep the numbers above with
two differences of form: Mosaic has no ``Precision.HIGH``, so the
inverse's float32 operands are split into bfloat16 high and low parts by
hand (the same three passes), and the sums of g are products with a 0/1
triangle over g split three ways (exact products, float32 sums). Their
chunk is their own (``KERNEL_CHUNK``; a grid step walks a tile of 128
tokens): ``[kda] ... impl=pallas|xla`` says once a shape which path ran,
with the chunk in use, and utils/flops.py counts with it
(:func:`chunk_in_use`). The same gate decides for what shapes the core's
inputs (ops/kda_inputs.py: the convolution, SiLU, unit norm and decay
gate between the projections and this core): a kernel pair on the rows the
core's kernels read, so no layout copy stands between them, or XLA's chain.

Memory. XLA path: the sequence is walked in segments of ``segment_chunks``
chunks under ``jax.checkpoint``: the backward pass keeps one state a
segment and recomputes a segment's chunk quantities and chunk states when
it gets there, so no per-token state and no whole-sequence (C, C) table is
ever kept. Kernel path: the forward kernel leaves the float32 state each
tile of 128 tokens starts from (268 MB a layer at (2, 8192, 32, 128)); the
backward kernel recomputes a tile's tables and re-walks its chunks from
that state, so nothing else is saved beside the inputs.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.ops import attention

BLOCK = 16  # rows that share a reference point. Bounded gate: the point is
# the block's middle, and a masked pair of one block still multiplies
# exp(+8 |lower_bound|) twice, so that form needs BLOCK * |lower_bound| < 88
# (kda_chunked refuses a bound past it). Unbounded gate: the point is the
# block's first row, every factor is <= 1, the block's own pairs are taken
# by subtraction, and no bound is needed.

# tokens a chunk: the hybrid cell's whole step on the v5e reads 1263 ms at
# 32 against 1319 at 64 (PERF.md section 6, PR 26)
DEFAULT_CHUNK = 32
DEFAULT_SEGMENT_CHUNKS = 4
# The kernels' own (ops/kda_kernel.py): tokens a chunk and heads a grid
# step, read through the hybrid cell's whole step (PERF.md section 6, PR 31)
KERNEL_CHUNK = 64
KERNEL_HEADS = 8
SCOPE = "kda_chunk"  # jax.named_scope round the core
_MASKED = -1e30  # exp() of it is 0, with a zero derivative
_SOLVE = jax.lax.Precision.HIGH  # the inverse's float32 products

_logged: set[tuple] = set()


def _interpret() -> bool:
    """Mosaic on a TPU; elsewhere the interpreter, which only a test that
    steers the gate below ever reaches."""
    return not attention._on_tpu()


def unsupported(S: int, d_k: int, d_v: int, dtype=jnp.bfloat16,
                cp=None) -> str | None:
    """Why the kernels of ops/kda_kernel.py do not take this core, or None
    when they do: from what a call can see, no option."""
    if not attention._on_tpu():
        return "the backend is not a TPU"
    from pytorch_distributed_train_tpu.ops.kda_kernel import TILE

    if d_k % 128 or d_v % 128:
        return f"d_k={d_k} d_v={d_v}: not multiples of 128"
    if S % TILE:
        return f"S={S} is not whole tiles of {TILE}"
    if dtype not in (jnp.bfloat16, jnp.float32):
        return f"operands in {jnp.dtype(dtype).name}"
    if cp is not None and cp.active:
        return "mesh: the sequence is sharded"
    return None


def chunk_in_use(S: int, d_k: int, d_v: int) -> int:
    """Tokens a chunk of the path :func:`kda_chunked` takes at its
    defaults for this shape (utils/flops.py counts the tables with it)."""
    if unsupported(S, d_k, d_v) is None:
        return KERNEL_CHUNK
    return min(DEFAULT_CHUNK, S)


def log_plan(S: int, chunk: int | None, heads: int, d_k: int, d_v: int,
             impl: str) -> None:
    """Say once a shape, at trace time, how the sequence is cut and what
    runs it (stderr, like the attention dispatch's line): ``impl=pallas``
    with the kernels' tile, or ``impl=xla`` with the scan's length and the
    reason. The input shaping (ops/kda_inputs.py) says ``inputs=pallas``
    with its tile or ``inputs=xla`` with the reason on a line of its own,
    which names no chunk."""
    key = (S, chunk, heads, d_k, d_v, impl)
    if key in _logged:
        return
    _logged.add(key)
    cut = (f"chunk={chunk} chunks={S // chunk} heads={heads} d_k={d_k} "
           f"d_v={d_v} state_dtype=float32" if chunk else
           f"heads={heads} d={d_k}")
    print(f"[kda] S={S} {cut} {impl}", file=sys.stderr, flush=True)


def kda_recurrent(q, k, v, g, beta):
    """Token by token. q, k, g: (B, S, H, d_k); v: (B, S, H, d_v); beta:
    (B, S, H). Everything in float32. Returns o (B, S, H, d_v)."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    B, S, H, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (B, H, .)
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state0 = jnp.zeros((B, H, dk, v.shape[-1]), f32)
    _, o = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(o, 0, 1)


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular ``a`` (..., C, C), float32:
    sum_k (-a)^k = (I - a)(I + a^2)(I + a^4)..., finite since a^C = 0."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=a.dtype)
    mm = lambda x, y: jnp.matmul(x, y, precision=_SOLVE)  # noqa: E731
    out, power = eye - a, a
    for _ in range(max(math.ceil(math.log2(C)) - 1, 0)):
        power = mm(power, power)
        out = mm(out, eye + power)
    return out


def _pair_tables(q, k, G, dtype, bounded):
    """``table(rows)``: sum_c rows_tc k_jc exp(G_tc - G_jc) for every pair
    of a chunk's tokens that the causal masks keep, (..., C, C) float32;
    what lies past the diagonal is for the caller to mask. The two forms of
    the module docstring."""
    f32 = jnp.float32
    C = q.shape[-2]
    n = C // BLOCK
    # reference point of each block of rows: the sum at its middle (a
    # bounded gate), at its first row (an unbounded one)
    R = G[..., (BLOCK // 2 - 1 if bounded else 0)::BLOCK, :]  # (..., n, dk)
    row_ref = jnp.repeat(R, BLOCK, axis=-2)  # (..., C, dk), of a row's block
    # bounded: within exp(+-BLOCK/2 |lower_bound|); unbounded: <= 1
    row_decay = jnp.exp(G - row_ref)
    # keys against each block's reference: (..., n, C, dk); keys after the
    # block (unbounded: and the block's own) are out of its reach and
    # masked before the exp
    block_of = jnp.arange(C) // BLOCK
    reach = block_of[None, :] <= jnp.arange(n)[:, None] if bounded \
        else block_of[None, :] < jnp.arange(n)[:, None]  # (n, C)
    expo = R[..., :, None, :] - G[..., None, :, :]
    key_decay = jnp.exp(jnp.where(reach[..., None], expo, _MASKED))
    keys = (k.astype(f32)[..., None, :, :] * key_decay).astype(dtype)

    def table(rows):
        rows = (rows.astype(f32) * row_decay).astype(dtype)
        rows = rows.reshape(*rows.shape[:-2], n, BLOCK, rows.shape[-1])
        t = jnp.einsum("...nik,...njk->...nij", rows, keys,
                       preferred_element_type=f32)
        return t.reshape(*t.shape[:-3], C, C)

    if bounded:
        return table

    # a block's own pairs: G_t - G_j by subtraction, never a factor above 1
    blocks = lambda x: x.astype(f32).reshape(  # noqa: E731
        *x.shape[:-2], n, BLOCK, x.shape[-1])
    Gb = blocks(G)
    lower = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))
    inside = jnp.exp(jnp.where(
        lower[..., None], Gb[..., :, None, :] - Gb[..., None, :, :],
        _MASKED))  # (..., n, i, j, dk)
    own_keys = blocks(k)[..., None, :, :] * inside
    eye = jnp.eye(n, dtype=f32)

    def with_own_block(rows):
        # (a sum, not a product the backend may round to bfloat16 passes)
        own = jnp.sum(blocks(rows)[..., :, None, :] * own_keys, -1)
        # block n's (i, j) to row n BLOCK + i, column n BLOCK + j
        own = own[..., :, :, None, :] * eye[:, None, :, None]
        return table(rows) + own.reshape(*own.shape[:-4], C, C)

    return with_own_block


def _chunk_tables(q, k, v, g, beta, dtype, bounded=True):
    """What a chunk needs that no state enters. Inputs (..., C, d) with the
    chunk's tokens on the second-to-last axis; g float32. Returns
    (qg, bmat, w, u0, kend, gend): Q exp G, B, W, U0, K exp(G_C - G) and
    exp(G_C)."""
    f32 = jnp.float32
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-2)  # inclusive, (..., C, dk)
    table = _pair_tables(q, k, G, dtype, bounded)
    tri = jnp.tril(jnp.ones((C, C), bool))
    bmat = jnp.where(tri, table(q), 0.0)
    a = jnp.where(jnp.tril(tri, -1), table(k), 0.0) * beta[..., None]
    inv = _unit_lower_inverse(a)
    gamma = jnp.exp(G)
    rhs = jnp.concatenate(
        [k.astype(f32) * gamma, v.astype(f32)], axis=-1) * beta[..., None]
    sol = jnp.matmul(inv, rhs, precision=_SOLVE)
    w, u0 = sol[..., :k.shape[-1]], sol[..., k.shape[-1]:]
    qg = q.astype(f32) * gamma
    kend = k.astype(f32) * jnp.exp(G[..., -1:, :] - G)
    return (qg.astype(dtype), bmat.astype(dtype), w.astype(dtype), u0,
            kend.astype(dtype), jnp.exp(G[..., -1, :]))


def _segment(state, xs, *, chunk, dtype, bounded=True):
    """One segment of whole chunks from ``state`` (B, H, dk, dv) float32.
    xs: q, k, v, g (B, L, H, d) and beta (B, L, H). Returns (state', o)."""
    f32 = jnp.float32
    q, k, v, g, beta = xs
    B, L, H, _ = q.shape
    n = L // chunk

    def chunks(x):  # (B, L, H, ...) -> (n, B, H, chunk, ...)
        x = x.reshape(B, n, chunk, H, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    tables = _chunk_tables(chunks(q), chunks(k), chunks(v),
                           chunks(g).astype(f32),
                           chunks(beta).astype(f32), dtype, bounded)

    def step(s, t):
        qg, bmat, w, u0, kend, gend = t
        sd = s.astype(dtype)
        dot = lambda eq, x, y: jnp.einsum(  # noqa: E731
            eq, x, y, preferred_element_type=f32)
        u = u0 - dot("bhck,bhkv->bhcv", w, sd)
        o = dot("bhck,bhkv->bhcv", qg, sd) \
            + dot("bhcj,bhjv->bhcv", bmat, u.astype(dtype))
        s = s * gend[..., None] + dot("bhck,bhcv->bhkv", kend,
                                      u.astype(dtype))
        return s, o

    state, o = jax.lax.scan(step, state, tables)
    # (n, B, H, chunk, dv) -> (B, L, H, dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, L, H, -1)
    return state, o


def _form_said(bounded: bool) -> str:
    """The ``[kda]`` line's last field: only the second form is named."""
    return "" if bounded else " gate=unbounded"


def kda_chunked(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                segment_chunks: int = DEFAULT_SEGMENT_CHUNKS,
                lower_bound: float | None = -5.0, cp=None):
    """The chunked form (module docstring). q, k: (B, S, H, d_k), v:
    (B, S, H, d_v), their dtype is the products' operand dtype; g:
    (B, S, H, d_k) float32 log-decay; beta: (B, S, H) in [0, 2].
    ``lower_bound`` is the decay gate's FORM, which the mixer knows from
    its gate: a number where g lies in [lower_bound, 0] (the blocks' product
    form leans on it), None where g is unbounded below (no factor above 1
    is formed; module docstring).
    S must be whole chunks and ``chunk`` whole blocks of 16. Returns o in
    q's dtype; the state and every sum of g stay float32.

    On a TPU, at head widths and a length its tiles fit, the kernel pair of
    ops/kda_kernel.py takes the core at its own chunk (``chunk`` and
    ``segment_chunks`` cut the XLA scan alone); ``cp`` is the mesh's axes
    (ops/attention.py) or None: batch and heads are independent, so under
    a mesh the kernels run a device on its own block."""
    B, S, H, dk = q.shape
    bounded = lower_bound is not None
    if bounded and BLOCK * abs(lower_bound) >= 88.0:
        # the bounded form's one reliance (module docstring); the
        # unbounded form has none and takes any g <= 0
        raise ValueError(
            f"a log-decay as low as {lower_bound} overflows float32 over a "
            f"block of {BLOCK} tokens in the bounded gate's product form; "
            f"lower_bound=None takes an unbounded gate")
    if chunk % BLOCK or S % chunk:
        raise ValueError(
            f"kda: chunk {chunk} must be a multiple of {BLOCK} and divide "
            f"the sequence length {S}")
    why = unsupported(S, dk, v.shape[-1], q.dtype, cp)
    if why is None:
        return _kda_kernels(q, k, v, g, beta, cp, bounded)
    log_plan(S, chunk, H, dk, v.shape[-1],
             f"impl=xla reason={why}{_form_said(bounded)}")
    return _kda_scan(q, k, v, g, beta, chunk, segment_chunks, bounded)


def _kda_scan(q, k, v, g, beta, chunk, segment_chunks, bounded=True):
    """The XLA path: a scan over segments of whole chunks."""
    B, S, H, dk = q.shape
    n_chunks = S // chunk
    seg = math.gcd(n_chunks, max(segment_chunks, 1)) * chunk
    n_seg = S // seg
    g = g.astype(jnp.float32)

    def segments(x):  # (B, S, ...) -> (n_seg, B, seg, ...)
        return jnp.moveaxis(x.reshape(B, n_seg, seg, *x.shape[2:]), 1, 0)

    body = jax.checkpoint(
        lambda s, xs: _segment(s, xs, chunk=chunk, dtype=q.dtype,
                               bounded=bounded))
    state0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    # The core's name in every device operation's op_name, forward and (as
    # ``transpose(jvp(kda_chunk))``) backward: what a per-layer metric of
    # the scan would select by (PERF.md section 7 (3)).
    with jax.named_scope(SCOPE):
        _, o = jax.lax.scan(body, state0,
                            tuple(segments(x) for x in (q, k, v, g, beta)))
        o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, -1)
    return o.astype(q.dtype)


def on_own_block(local, cp, like, specs):
    """``local`` as it runs a device: itself on one device, else inside a
    manual region over the axes that shard batch and heads (GSPMD cannot
    partition a Mosaic call: ops/attention.py ``_sharded_flash``).
    ``like``: a (B, S, H, d) operand; ``specs(spec)`` gives the region's
    (in_specs, out_specs) from that operand's spec."""
    if cp is None or cp.mesh.size == 1:
        return local
    from pytorch_distributed_train_tpu.ops.cp_common import qkv_spec
    from pytorch_distributed_train_tpu.utils.compat import shard_map

    spec = qkv_spec(like, like, cp.mesh, context_axis=None,
                    batch_axes=cp.batch_axes, tensor_axis=cp.tensor_axis)
    in_specs, out_specs = specs(spec)
    return shard_map(local, mesh=cp.mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _kda_kernels(q, k, v, g, beta, cp, bounded=True):
    """The kernel pair, a device on its own block of batch and heads."""
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_train_tpu.ops import kda_kernel

    def local(q, k, v, g, beta):
        H = q.shape[2]
        hb = next(n for n in range(KERNEL_HEADS, 0, -1) if H % n == 0)
        log_plan(q.shape[1], KERNEL_CHUNK, H, q.shape[3], v.shape[3],
                 f"impl=pallas tile={kda_kernel.TILE} chunks_per_step="
                 f"{kda_kernel.TILE // KERNEL_CHUNK} heads_per_step={hb}"
                 f"{_form_said(bounded)}")
        with jax.named_scope(SCOPE):
            return kda_kernel.kda_pallas(
                q, k, v, g, beta,
                kda_kernel.Plan(KERNEL_CHUNK, hb, _interpret(), bounded))

    return on_own_block(
        local, cp, q,
        lambda spec: ((spec,) * 4 + (P(*spec[:3]),), spec))(q, k, v, g, beta)
