"""Attention core with a single dispatch point.

All transformer models route through :func:`dot_product_attention`, so the
implementation (XLA einsum path vs Pallas flash kernel) is swappable without
touching model code — the analogue of torch's `scaled_dot_product_attention`
backend dispatch, but resolved statically.

Shapes follow the TPU-friendly convention (batch, seq, heads, head_dim) —
"BSHD" — which keeps the head dim last (lane dim, 128-multiple for the MXU)
and avoids the NCHW-style transposes torch attention does.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp

# Process-wide default for impl="auto" callers. Models thread their own
# ModelConfig.attention_impl as a static module attr, so this global is
# only the operator-level control. Resolution order for an "auto" call:
# PDTT_ATTENTION_IMPL env var > set_default_impl() > "auto" heuristic.
# The torch analogue is the global torch.backends.cuda.sdp_kernel switch.
_default_impl = "auto"

_VALID_IMPLS = ("auto", "xla", "pallas", "chunked")


def set_default_impl(impl: str) -> None:
    if impl not in _VALID_IMPLS:
        raise ValueError(
            f"attention impl must be one of {_VALID_IMPLS}, got {impl!r}")
    global _default_impl
    _default_impl = impl


def _env_impl() -> str | None:
    env = os.environ.get("PDTT_ATTENTION_IMPL")
    if env is not None and env not in _VALID_IMPLS:
        raise ValueError(
            f"PDTT_ATTENTION_IMPL must be one of {_VALID_IMPLS}, got {env!r}"
        )
    return env


def _resolve_default_impl() -> str:
    return _env_impl() or _default_impl


# The checkpoint_name of what the Pallas kernel's forward hands back, its
# output and log-sum-exp (flash_attention.py `_flash_fwd` tags them): a
# remat policy that names it (models/remat.py: every one) keeps the two,
# (B, S, H x Dv) in the input's dtype and (B, H, S) fp32 a call, and the
# backward kernels read them; one that does not runs the forward kernel
# again for them. Defined here and not beside the tag so that the policies'
# module, which every training step imports, does not import Pallas.
FLASH_RESIDUALS_NAME = "flash_out+lse"

_resolutions_logged: set[tuple] = set()


def _log_resolution(impl: str, q, k, *, causal: bool, window: int,
                    interpret: bool = False, plan=None, fetch=None,
                    bwd=None) -> None:
    """Say once per distinct call signature, at trace time, which
    implementation the dispatch resolved to — a run's log then shows
    whether the Pallas kernel really took the call, and with ``plan``
    (flash_attention.tile_plan of the call) how many of a head's score
    tiles it enters and how many of those build a mask: ``tiles=3/4
    masked=2``; with ``fetch`` (flash_attention.block_map's plan: the
    index maps the kernels' calls themselves use) how many of a head's
    grid steps enter a tile and how many blocks of K and V the head's walk
    fetches, forward and again in the fused backward: ``steps=72/128
    fetches=70``; with ``bwd`` (flash_attention.backward_plan of the call:
    the function the kernel's backward itself asks) the backward's form
    and the bytes it would keep in VMEM under a KV head: ``bwd=fused
    resident=1.6MB``, or ``bwd=split`` past the budget. On stderr: stdout
    is the product of the generation CLIs."""
    key = (impl, q.shape, k.shape[2], str(q.dtype), causal, window, interpret)
    if key in _resolutions_logged:
        return
    _resolutions_logged.add(key)
    kernel = "" if plan is None else \
        f" tiles={plan.executed}/{plan.total} masked={plan.masked}"
    for more in (fetch, bwd):
        if more is not None:
            kernel += f" {more}"
    print(f"[attention] impl={impl} q={tuple(q.shape)} kv_heads={k.shape[2]} "
          f"dtype={q.dtype} causal={causal} window={window} "
          f"interpret={interpret}{kernel}", file=sys.stderr, flush=True)


@dataclasses.dataclass(frozen=True)
class ContextParallelConfig:
    """Static recipe for sequence/context parallelism (SURVEY §5.7, §2.3).

    Passed down from the mesh config to attention modules; hashable so flax
    modules can hold it as a static attribute. ``impl``:
      ring    — lax.ppermute KV rotation, scales to any axis size
      ulysses — all-to-all head↔seq swap, needs heads % axis size == 0

    Built for every mesh of more than one device, not only when the
    context axis is > 1 (``active``): a Mosaic kernel cannot be
    partitioned by GSPMD, so under any sharded mesh the flash kernel runs
    in a shard_map region over this mesh's batch/tensor axes
    (``_sharded_flash``).
    """

    mesh: jax.sharding.Mesh
    impl: str = "ring"  # ring | ulysses
    # Ring sequence layout: "zigzag" pairs chunk i with 2n−1−i per device
    # so causal work balances across the ring (ring_attention.zigzag_perm);
    # ignored by ulysses and by non-causal calls.
    layout: str = "contiguous"  # contiguous | zigzag
    context_axis: str = "context"
    batch_axes: tuple[str, ...] = ("data", "fsdp")
    tensor_axis: str | None = "tensor"

    @property
    def active(self) -> bool:
        return self.mesh.shape[self.context_axis] > 1

    def activation_sharding(self, ndim: int) -> jax.sharding.NamedSharding:
        """(B, S, ...) activation sharding: batch over batch_axes, seq over
        the context axis — the constraint models apply so pre/post-attention
        pointwise compute stays seq-sharded instead of replicating."""
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec(tuple(self.batch_axes), self.context_axis,
                             *([None] * (ndim - 2)))
        return NamedSharding(self.mesh, spec)


def dot_product_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, H_kv, D)
    v: jax.Array,  # (B, Sk, H_kv, D)
    *,
    causal: bool = False,
    mask: jax.Array | None = None,  # (B, 1, Sq, Sk) or broadcastable, True=keep
    softmax_dtype: jnp.dtype = jnp.float32,
    impl: str = "auto",  # auto | xla | pallas | chunked
    cp: ContextParallelConfig | None = None,
    window: int = 0,  # >0: sliding window — attend to the last `window` keys
    segments: jax.Array | None = None,  # (B, S) ids; attend only within ==
) -> jax.Array:
    """Multi-head attention core, GQA-aware.

    Softmax is always computed in fp32 (``softmax_dtype``) regardless of the
    bf16 compute policy — the TPU replacement for autocast's per-op allowlist
    keeping softmax in fp32 (SURVEY C18).

    With an *active* ``cp`` the sequence dim is sharded over the context mesh
    axis and the core routes through ring attention or Ulysses (SURVEY §5.7)
    inside a shard_map region embedded in the surrounding GSPMD program.
    Contract under cp: Ulysses forwards ``impl`` to its local full-sequence
    core; ring attention is its own implementation (``impl`` does not apply)
    and always does fp32 chunk softmax — same as the default
    ``softmax_dtype``, which cp paths do not override.
    """
    if impl not in _VALID_IMPLS:
        raise ValueError(
            f"attention impl must be one of {_VALID_IMPLS}, got {impl!r}")
    if window:
        # Mistral-style sliding window: only defined relative to causal
        # ordering (each query sees its trailing `window` keys). Composes
        # with every backend: xla/chunked mask or band-slice, pallas masks
        # within tiles and skips out-of-band blocks, ring skips whole
        # out-of-band hops, ulysses applies it on the full-seq local core.
        if window < 0:
            raise ValueError(f"window must be >= 0, got {window}")
        if not causal:
            raise ValueError("window attention requires causal=True")
    # The env var is the operator's kill switch: it beats EVERYTHING,
    # including an explicit impl arg or a config-threaded backend.
    env = _env_impl()
    if env is not None:
        impl = env
    elif impl == "auto":
        impl = _default_impl
    if segments is not None:
        # Packed-document isolation (models pass the (B, S) segment ids,
        # NOT a materialised (B, 1, S, S) mask — the xla path builds it
        # where it materialises S^2 scores anyway, the chunked path
        # builds one (B, 1, chunk, Sk) tile at a time).
        if q.shape[1] != k.shape[1]:
            raise ValueError("segments requires self-attention shapes")
        if impl == "pallas":
            raise ValueError(
                "the pallas flash kernel does not take segment ids "
                "(packed-document isolation) — use impl='xla' or "
                "'chunked' for segment_eos_id runs")
        if cp is not None and cp.active:
            raise NotImplementedError(
                "segments with context parallelism is unsupported")
    if cp is not None and cp.active:
        if cp.impl == "ring":
            if mask is not None:
                raise NotImplementedError(
                    "ring attention supports causal masking only; use "
                    "context_impl='ulysses' for padded/arbitrary masks"
                )
            from pytorch_distributed_train_tpu.ops.ring_attention import (
                ring_attention,
            )

            return ring_attention(
                q, k, v, mesh=cp.mesh, causal=causal, window=window,
                impl=impl, layout=cp.layout, context_axis=cp.context_axis,
                batch_axes=cp.batch_axes, tensor_axis=cp.tensor_axis,
            )
        if cp.impl == "ulysses":
            from pytorch_distributed_train_tpu.ops.ulysses import (
                ulysses_attention,
            )

            return ulysses_attention(
                q, k, v, mask=mask, mesh=cp.mesh, causal=causal,
                window=window, context_axis=cp.context_axis,
                batch_axes=cp.batch_axes,
                tensor_axis=cp.tensor_axis, impl=impl,
            )
        raise ValueError(f"unknown context_impl {cp.impl!r}")
    if impl in ("auto", "pallas") and segments is None:
        from pytorch_distributed_train_tpu.ops import flash_attention as _fa

        on_tpu = _on_tpu()
        if _fa.supported(q, k, v, causal=causal, mask=mask, window=window):
            # impl='pallas' forces the kernel anywhere (interpret mode off-TPU
            # — slow but exact, which is what tests and debugging want);
            # 'auto' uses it only on a TPU, at the lengths where it pays
            # off. On a TPU interpret is never true, and a kernel the
            # compiler refuses raises: nothing below catches it.
            if impl == "pallas" or (on_tpu and _fa.profitable(q)):
                # GQA is native in the kernel (KV BlockSpec index_map
                # b // rep) — no expanded K/V copy in HBM.
                _log_resolution(
                    "pallas", q, k, causal=causal, window=window,
                    interpret=not on_tpu,
                    plan=_fa.call_plan(q, k, causal=causal, window=window),
                    fetch=_fa.call_fetch_plan(q, k, causal=causal,
                                              window=window),
                    bwd=_fa.call_backward_plan(q, k, v))
                flash = functools.partial(
                    _fa.flash_attention, causal=causal, window=window,
                    interpret=not on_tpu)
                if cp is not None and cp.mesh.size > 1:
                    return _sharded_flash(flash, q, k, v, cp)
                return flash(q, k, v)
        elif impl == "pallas":
            raise ValueError("pallas flash attention unsupported for these shapes")
    if impl == "chunked" or (impl == "auto" and q.shape[1] >= _AUTO_CHUNK_MIN_SEQ):
        # auto → chunked at training-length sequences when the Pallas kernel
        # didn't take the call above: it fits shapes the dense path runs
        # out of memory on (speed not measured on today's stack).
        _log_resolution("chunked", q, k, causal=causal, window=window)
        return _chunked_attention(q, k, v, causal=causal, mask=mask,
                                  softmax_dtype=softmax_dtype, window=window,
                                  segments=segments)
    if segments is not None:
        seg_mask = (segments[:, None, :, None] == segments[:, None, None, :])
        mask = seg_mask if mask is None else (mask & seg_mask)
    _log_resolution("xla", q, k, causal=causal, window=window)
    return _xla_attention(q, k, v, causal=causal, mask=mask,
                          softmax_dtype=softmax_dtype, window=window)


def _sharded_flash(flash, q, k, v, cp: ContextParallelConfig):
    """The flash kernel under a sharded mesh: a manual (shard_map) region
    with batch over the batch axes and heads over the tensor axis, each
    device running the kernel on its own block. GSPMD cannot partition a
    Mosaic call itself ("Mosaic kernels cannot be automatically
    partitioned"), and attention needs no communication across batch or
    heads, so the region holds no collective. The context axis is 1 here
    (an active cp took the ring/Ulysses route above)."""
    from pytorch_distributed_train_tpu.ops.cp_common import qkv_spec
    from pytorch_distributed_train_tpu.utils.compat import shard_map

    spec = qkv_spec(q, k, cp.mesh, context_axis=cp.context_axis,
                    batch_axes=cp.batch_axes, tensor_axis=cp.tensor_axis)
    return shard_map(flash, mesh=cp.mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def _on_tpu() -> bool:
    try:
        return jax.devices()[0].platform == "tpu"
    except RuntimeError:
        return False


def _xla_attention(q, k, v, *, causal, mask, softmax_dtype, window=0):
    from pytorch_distributed_train_tpu.ops.cp_common import expand_kv_heads

    orig_dtype = q.dtype
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    # GQA: repeat KV heads up to H (XLA fuses the broadcast into the matmul)
    k, v = expand_kv_heads(k, v, H)

    scale = 1.0 / jnp.sqrt(D).astype(softmax_dtype)
    # (B, H, Sq, Sk)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=softmax_dtype)
    logits = logits * scale

    if causal:
        q_pos = jnp.arange(Sq)[:, None] + (Sk - Sq)  # align ends for KV-cache decode
        k_pos = jnp.arange(Sk)[None, :]
        causal_mask = q_pos >= k_pos
        if window:
            causal_mask &= (q_pos - k_pos) < window
        logits = jnp.where(causal_mask[None, None], logits, _neg_inf(softmax_dtype))
    if mask is not None:
        logits = jnp.where(mask, logits, _neg_inf(softmax_dtype))

    probs = jax.nn.softmax(logits, axis=-1).astype(orig_dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _neg_inf(dtype) -> jax.Array:
    return jnp.asarray(jnp.finfo(dtype).min, dtype)


# Query-chunk size for impl="chunked". 256 keeps the per-chunk logits tile
# MXU-friendly while bounding live attention memory to O(chunk * Sk).
_CHUNK_Q = 256

# impl="auto" switches from dense XLA to the chunked path at this query
# length (≥4 tiles — below that the map/remat overhead outweighs the
# saved HBM traffic; not measured on today's stack).
_AUTO_CHUNK_MIN_SEQ = 1024


def _chunked_attention(q, k, v, *, causal, mask, softmax_dtype,
                       chunk: int = _CHUNK_Q, window: int = 0,
                       segments=None):
    """Memory-efficient attention in pure XLA: flash-attention's streaming
    structure (process the score matrix in tiles, never materialise it
    whole) expressed as a sequential `lax.map` over query chunks with the
    chunk body rematerialised.

    Motivation: the plain XLA path keeps O(Sq*Sk) bf16 score/remat temps
    live through the backward, which at training lengths can exceed the
    chip's HBM on its own. Here the forward
    holds one (B, H, chunk, Sk) fp32 tile at a time, and `jax.checkpoint`
    on the body makes the backward recompute tiles instead of storing them
    — the same FLOPs-for-HBM trade the Pallas flash kernel makes, minus the
    hand-written kernel, so it compiles on any backend.

    Numerics match `_xla_attention` exactly per chunk: fp32 scores, full
    row softmax over Sk (no online rescaling needed — each query row sees
    all keys within its tile), output cast back to the input dtype.
    """
    from pytorch_distributed_train_tpu.ops.cp_common import expand_kv_heads

    orig_dtype = q.dtype
    B, Sq, H, D = q.shape
    _, Sk, _, _ = k.shape
    k, v = expand_kv_heads(k, v, H)
    if Sq <= chunk:
        if segments is not None:
            seg_mask = (segments[:, None, :, None]
                        == segments[:, None, None, :])
            mask = seg_mask if mask is None else (mask & seg_mask)
        return _xla_attention(q, k, v, causal=causal, mask=mask,
                              softmax_dtype=softmax_dtype, window=window)

    n_chunks = -(-Sq // chunk)
    pad = n_chunks * chunk - Sq
    q_padded = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if mask is not None and mask.ndim < 4:
        # Honor the dense path's broadcastable-mask contract: left-pad
        # dims exactly as numpy broadcasting against (B, H, Sq, Sk) would,
        # so dim 2 is the query axis for the tile slicing below.
        mask = mask.reshape((1,) * (4 - mask.ndim) + mask.shape)
    if mask is not None and mask.shape[2] > 1 and pad:
        # Keep tile slices aligned: dynamic_slice clamps at the edge, which
        # would shift the last tile's window. Padded rows are fully masked;
        # their (uniform-softmax) outputs are dropped by the final slice.
        mask = jnp.pad(mask, ((0, 0),) * 2 + ((0, pad), (0, 0)),
                       constant_values=False)
    # (n, B, chunk, H, D) — leading axis is the map axis
    q_tiles = q_padded.reshape(B, n_chunks, chunk, H, D).transpose(1, 0, 2, 3, 4)
    starts = jnp.arange(n_chunks) * chunk

    scale = 1.0 / jnp.sqrt(D).astype(softmax_dtype)
    k_pos = jnp.arange(Sk)[None, :]
    # Sliding window: each tile's queries only see keys in
    # [start - window + 1, start + chunk) — slice K/V to that static-width
    # band instead of scoring (and masking away) the whole key axis:
    # O(Sq * window) work, the compute win windowing exists for. Only when
    # no explicit mask rides along (its key axis would need slicing too).
    if segments is not None and pad:
        # padded query rows get segment id -1: they match nothing real
        seg_padded = jnp.pad(segments, ((0, 0), (0, pad)),
                             constant_values=-1)
    else:
        seg_padded = segments
    band_width = min(Sk, (window + chunk - 1)) if window else Sk
    use_band = (bool(window) and mask is None and segments is None
                and band_width < Sk)

    def body(args):
        q_tile, start = args
        if use_band:
            band_start = jnp.clip(start + (Sk - Sq) - (window - 1),
                                  0, Sk - band_width)
            k_t = jax.lax.dynamic_slice_in_dim(k, band_start, band_width, 1)
            v_t = jax.lax.dynamic_slice_in_dim(v, band_start, band_width, 1)
            k_pos_t = (band_start + jnp.arange(band_width))[None, :]
        else:
            k_t, v_t, k_pos_t = k, v, k_pos
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_tile, k_t,
                            preferred_element_type=softmax_dtype) * scale
        q_pos = start + jnp.arange(chunk)[:, None] + (Sk - Sq)
        if causal:
            keep = q_pos >= k_pos_t
            if window:
                keep &= (q_pos - k_pos_t) < window
            logits = jnp.where(keep[None, None], logits,
                               _neg_inf(softmax_dtype))
        if mask is not None:
            # mask is (B, 1, Sq, Sk) or broadcastable; slice the query axis
            # when it is materialised, else broadcast as-is.
            if mask.shape[2] == 1:
                tile_mask = mask
            else:
                tile_mask = jax.lax.dynamic_slice_in_dim(mask, start, chunk,
                                                         axis=2)
            logits = jnp.where(tile_mask, logits, _neg_inf(softmax_dtype))
        if seg_padded is not None:
            # one (B, 1, chunk, Sk) segment tile at a time — never the
            # full (B, 1, Sq, Sk) mask (the whole point of this path)
            seg_q = jax.lax.dynamic_slice_in_dim(seg_padded, start, chunk,
                                                 axis=1)
            seg_tile = (seg_q[:, None, :, None]
                        == seg_padded[:, None, None, :Sk])
            logits = jnp.where(seg_tile, logits, _neg_inf(softmax_dtype))
        # Padded query rows (beyond Sq) mask everything out → uniform
        # softmax over garbage; harmless, dropped by the final slice.
        probs = jax.nn.softmax(logits, axis=-1).astype(orig_dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v_t)

    out_tiles = jax.lax.map(jax.checkpoint(body), (q_tiles, starts))
    # V's own head dim (latent attention: 192-deep scores, 128-deep values)
    out = out_tiles.transpose(1, 0, 2, 3, 4).reshape(
        B, n_chunks * chunk, H, v.shape[-1])
    return out[:, :Sq]
