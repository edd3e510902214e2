"""Ring attention: context parallelism over the ICI ring (SURVEY §5.7).

The TPU-native replacement for torch's experimental context parallelism
(torch:distributed/tensor/experimental/_context_parallel/_attention.py:317
`_templated_ring_attention`, :242 `_RingRotater`): the sequence dim is
sharded over the ``'context'`` mesh axis; each device keeps its Q shard
resident and K/V shards rotate one hop per step around the ring via
``lax.ppermute`` — neighbor ICI links, no switch contention. Chunk outputs
merge with the flash-attention logsumexp rule, so the full (S, S) score
matrix never exists anywhere.

Key properties:
- **Comm/compute overlap**: the next hop's ppermute is issued before the
  current chunk's matmuls, so XLA's latency-hiding scheduler overlaps the
  ICI transfer with MXU work.
- **Causal skipping**: steps whose whole K/V chunk sits above the diagonal
  are skipped with ``lax.cond`` (the torch module's round-robin
  load-balancer answers the same problem — reference `_load_balancer.py`).
  Masks are position-based, so any sequence layout (contiguous or
  zigzag/load-balanced) works by passing the right position arrays.
- **Backward = reverse ring**: the forward is written in plain JAX, so
  autodiff transposes each ppermute into the opposite-direction rotation —
  exactly the hand-written backward of the torch impl (:488) — and
  ``jax.checkpoint`` on the chunk keeps residual memory at O(S_local).

Called inside ``shard_map`` (use :func:`ring_attention` for the global-array
wrapper). Softmax math is fp32 regardless of input dtype (ops.attention
policy).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from pytorch_distributed_train_tpu.utils.compat import shard_map

NEG_INF = -1e30

P = PartitionSpec


def _chunk_attention(q, k, v, q_pos, kv_pos, *, causal: bool, scale: float,
                     window: int = 0):
    """Attention of a local Q block against ONE K/V chunk.

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D); positions: (Sq,), (Sk,) global.
    Returns (o, lse): o normalized within the chunk (B, Sq, H, D) fp32,
    lse (B, H, Sq) fp32. Fully-masked rows get o=0, lse=NEG_INF — the merge
    rule then gives them zero weight. ``window`` > 0 adds the Mistral band
    (query attends its trailing ``window`` positions; requires causal,
    enforced upstream).
    """
    from pytorch_distributed_train_tpu.ops.cp_common import expand_kv_heads

    k, v = expand_kv_heads(k, v, q.shape[2])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        mask = q_pos[:, None] >= kv_pos[None, :]  # (Sq, Sk)
        if window:
            mask &= (q_pos[:, None] - kv_pos[None, :]) < window
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, H, Sq)
    # Rows with every entry masked: m == NEG_INF → treat as empty chunk.
    empty = m <= NEG_INF / 2
    p = jnp.exp(s - jnp.where(empty, 0.0, m)[..., None])
    p = jnp.where(empty[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)  # (B, H, Sq)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bkhd->bqhd", p / l_safe[..., None],
                   v.astype(jnp.float32))
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
    return o, lse


def _merge(o_a, lse_a, o_b, lse_b):
    """Combine two chunk-normalized attention results (flash merge rule)."""
    lse_new = jnp.logaddexp(lse_a, lse_b)  # (B, H, Sq)
    w_a = jnp.exp(lse_a - lse_new)
    w_b = jnp.exp(lse_b - lse_new)
    # transpose weights (B,H,Sq) → (B,Sq,H,1) to match o layout
    wt = lambda w: jnp.transpose(w, (0, 2, 1))[..., None]  # noqa: E731
    return o_a * wt(w_a) + o_b * wt(w_b), lse_new


def ring_attention_local(
    q: jax.Array,  # (B, Sq_local, H, D) — this device's Q shard
    k: jax.Array,  # (B, Sk_local, Hkv, D)
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = False,
    window: int = 0,
    q_pos: jax.Array | None = None,  # (Sq_local,) global positions
    kv_pos: jax.Array | None = None,
    chunk_impl: str = "einsum",  # einsum | pallas
    interpret: bool = False,  # pallas chunks: interpret mode (tests/CPU)
) -> jax.Array:
    """Ring attention body — call inside shard_map with seq sharded on
    ``axis_name``. Positions default to the contiguous layout
    (shard i owns [i*S_local, (i+1)*S_local)); pass explicit positions for a
    load-balanced (zigzag) layout.

    ``chunk_impl='pallas'`` runs each hop's local attention through the
    Pallas flash chunk kernel (flash_attention.flash_attention_chunk) —
    same (o, lse) contract, O(block) VMEM instead of the einsum path's
    materialized (Sq, Sk) fp32 scores. ``window`` > 0 applies the sliding
    band; whole out-of-band hops are skipped like above-diagonal ones.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    idx = jax.lax.axis_index(axis_name)
    if q_pos is None:
        q_pos = idx * Sq + jnp.arange(Sq)
    if kv_pos is None:
        kv_pos = idx * Sk + jnp.arange(Sk)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    if chunk_impl == "pallas":
        from pytorch_distributed_train_tpu.ops import flash_attention as _fa

        # The rotating chunks carry Hkv (not H) heads over ICI — an
        # H/Hkv reduction of ring traffic, the scarce resource here —
        # and since r4 the kernel takes them UNEXPANDED too (in-kernel
        # b // rep KV sharing): no local HBM broadcast per hop, and the
        # kernel's rep-axis dK/dV accumulation hands back Hkv-sized
        # cotangents that rotate at Hkv size in the backward.
        def chunk(q_, k_, v_, qp, kp):
            return _fa.flash_attention_chunk(
                q_, k_, v_, qp, kp, causal=causal, window=window,
                interpret=interpret)

        chunk = jax.checkpoint(chunk)
    elif chunk_impl == "einsum":
        chunk = jax.checkpoint(
            functools.partial(_chunk_attention, causal=causal, scale=scale,
                              window=window)
        )
    else:
        raise ValueError(
            f"ring chunk_impl must be einsum|pallas, got {chunk_impl!r}")

    def masked_chunk(k_t, v_t, pos_t):
        """Chunk attention, skipped entirely when causality (or the window
        band) masks the whole chunk (the ppermute still runs — all devices
        stay in the ring)."""
        if not causal:
            return chunk(q, k_t, v_t, q_pos, pos_t)
        needed = jnp.max(q_pos) >= jnp.min(pos_t)
        if window:
            # Band intersection: some key within (q - window, q].
            needed &= jnp.max(pos_t) > jnp.min(q_pos) - window

        def skip(_q, _k, _v, _qp, _kp):
            return (
                jnp.zeros((B, Sq, H, D), jnp.float32),
                jnp.full((B, H, Sq), NEG_INF, jnp.float32),
            )

        return jax.lax.cond(needed, chunk, skip, q, k_t, v_t, q_pos, pos_t)

    o = jnp.zeros((B, Sq, H, D), jnp.float32)
    lse = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    k_t, v_t, pos_t = k, v, kv_pos
    for t in range(axis_size):
        if t < axis_size - 1:
            # Issue the next hop FIRST so the ICI transfer overlaps the
            # chunk's MXU work (XLA latency-hiding scheduler).
            k_n = jax.lax.ppermute(k_t, axis_name, perm)
            v_n = jax.lax.ppermute(v_t, axis_name, perm)
            pos_n = jax.lax.ppermute(pos_t, axis_name, perm)
        o_c, lse_c = masked_chunk(k_t, v_t, pos_t)
        o, lse = _merge(o, lse, o_c, lse_c)
        if t < axis_size - 1:
            k_t, v_t, pos_t = k_n, v_n, pos_n
    return o.astype(q.dtype)


def _resolve_chunk_impl(q, k, n_ring, impl: str):
    """Map an attention ``impl`` request onto (chunk_impl, interpret) for
    the ring body, mirroring dot_product_attention's pallas gating: an
    explicit 'pallas' forces the kernel anywhere (interpret off-TPU — what
    parity tests want); 'auto' takes it only on a TPU, at shard sizes where
    it pays; 'xla'/'chunked' keep the einsum path."""
    from pytorch_distributed_train_tpu.ops import attention as attention_lib
    from pytorch_distributed_train_tpu.ops import flash_attention as _fa

    if impl not in ("auto", "pallas"):
        return "einsum", False
    B, S, H, D = q.shape
    # chunk_supported / profitable gate on seq-shard and lane dims only —
    # the head count (however 'tensor' splits it) doesn't affect support.
    local = jax.ShapeDtypeStruct((B, S // n_ring, H, D), q.dtype)
    if not _fa.chunk_supported(local, local, local):
        if impl == "pallas":
            raise ValueError(
                "ring attention: pallas chunks unsupported for these local "
                f"shapes (S_local={S // n_ring}, D={D})")
        return "einsum", False
    on_tpu = attention_lib._on_tpu()
    if impl == "pallas":
        return "pallas", not on_tpu
    if on_tpu and _fa.profitable(local):
        return "pallas", False
    return "einsum", False


def zigzag_perm(S: int, n: int) -> "np.ndarray":
    """Permutation laying the sequence out zigzag over an n-device ring:
    split into 2n chunks; device i holds chunks (i, 2n-1-i).

    The causal load balancer (SURVEY §5.7; the torch CP module's
    `_load_balancer.py` answers the same problem): under a contiguous
    layout device 0's rows finish after one hop while device n-1 computes
    on every hop, so every ring step runs at the slowest device's pace and
    causality saves nothing. Pairing chunk i with chunk 2n-1-i gives every
    device the same causal-triangle area per hop; with the Pallas chunk
    backend the out-of-triangle BLOCKS inside each hop are skipped on the
    position predicate, realizing the ~2× causal saving. Returns the
    new→old index array; invert with argsort."""
    import numpy as np

    h = S // (2 * n)
    order = []
    for i in range(n):
        order.extend(range(i * h, (i + 1) * h))
        order.extend(range((2 * n - 1 - i) * h, (2 * n - i) * h))
    return np.asarray(order, np.int32)


def _zigzag_pos(idx, Sq: int, n: int):
    """Device idx's global positions under the zigzag layout (traced)."""
    h = Sq // 2
    lo = idx * h
    hi = (2 * n - 1 - idx) * h
    return jnp.concatenate([lo + jnp.arange(h), hi + jnp.arange(h)])


def ring_attention(
    q: jax.Array,  # (B, S, H, D) GLOBAL arrays
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    causal: bool = False,
    window: int = 0,
    impl: str = "auto",  # auto | xla | pallas | chunked (chunk backend)
    layout: str = "contiguous",  # contiguous | zigzag (causal balance)
    context_axis: str = "context",
    batch_axes: Sequence[str] = ("data", "fsdp"),
    tensor_axis: str | None = "tensor",
) -> jax.Array:
    """Global-array entry: shard_map wrapper over the mesh.

    Sequence dim shards on ``context_axis``, batch on ``batch_axes``, heads
    on ``tensor_axis`` — composing CP×DP×TP in one manual region embedded in
    the surrounding GSPMD program. ``impl`` selects the per-hop chunk
    backend (see _resolve_chunk_impl); ``window`` applies the sliding band
    across the ring (out-of-band hops are skipped). ``layout='zigzag'``
    (causal only) permutes the sequence so each device holds chunks
    (i, 2n−1−i) — equal causal work per hop (see zigzag_perm); attention is
    permutation-equivariant over keys and position-masked explicitly, so
    the result is exact. Costs one gather in + one gather out per call
    (GSPMD lowers them onto the context axis) — wins when S² compute
    dwarfs S·D movement, i.e. exactly the long-context regime CP targets.
    """
    from pytorch_distributed_train_tpu.ops.cp_common import qkv_spec

    n = mesh.shape[context_axis]
    S = q.shape[1]
    use_zigzag = (layout == "zigzag" and causal and n > 1
                  and S % (2 * n) == 0 and S == k.shape[1])
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"ring layout must be contiguous|zigzag, "
                         f"got {layout!r}")
    if S % n != 0 or k.shape[1] % n != 0:
        # Sequence can't shard over the ring (e.g. a probe batch at init
        # time) — run the plain core instead.
        from pytorch_distributed_train_tpu.ops import attention as attention_lib

        return attention_lib.dot_product_attention(q, k, v, causal=causal,
                                                   window=window, impl=impl)
    chunk_impl, interpret = _resolve_chunk_impl(q, k, n, impl)
    spec = qkv_spec(q, k, mesh, context_axis=context_axis,
                    batch_axes=batch_axes, tensor_axis=tensor_axis)

    if use_zigzag:
        import numpy as np

        p = zigzag_perm(S, n)
        perm, inv = jnp.asarray(p), jnp.asarray(np.argsort(p))
        q, k, v = (jnp.take(x, perm, axis=1) for x in (q, k, v))

        def fn(a, b, c):
            idx = jax.lax.axis_index(context_axis)
            pos = _zigzag_pos(idx, a.shape[1], n)
            return ring_attention_local(
                a, b, c, axis_name=context_axis, axis_size=n,
                causal=causal, window=window, q_pos=pos, kv_pos=pos,
                chunk_impl=chunk_impl, interpret=interpret)

        o = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=False)(q, k, v)
        return jnp.take(o, inv, axis=1)

    fn = functools.partial(
        ring_attention_local, axis_name=context_axis, axis_size=n,
        causal=causal, window=window, chunk_impl=chunk_impl,
        interpret=interpret,
    )
    return shard_map(
        lambda a, b, c: fn(a, b, c),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
