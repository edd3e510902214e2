"""Llama-2 decoder for pretraining (SURVEY H3; BASELINE.json:11).

Config 5 of the acceptance matrix: "Llama-2 7B pretrain, FSDP → XLA GSPMD
param sharding". Architecture: RMSNorm (pre-norm), rotary position
embeddings, GQA-capable attention, SwiGLU MLP, untied LM head — the Llama-2
recipe, sized by ModelConfig (7B = hidden 4096 / 32 layers / 32 heads /
mlp 11008 / vocab 32000).

TPU-first notes:
- Param layout is chosen for the FSDP×TP partition rules in
  parallel/partition.py::llama_rules (projection kernels keep hidden first so
  'fsdp' shards the big dim, 'tensor' the head dim).
- RoPE is precomputed per call at trace time — it folds into constants under
  jit; no cache buffers to shard.
- `remat=True` (the 7B preset default) checkpoints each block: standard
  HBM-for-FLOPs trade (SURVEY "jax.checkpoint / rematerialisation").
- Causal masking happens inside the attention core; no materialised (S,S)
  mask tensor at the model level.

The looped decoder (``loop_steps`` T > 1 with ``sandwich_norm``; preset
``ouro_2_6b_lm_l8``: Ouro-2.6B, arXiv:2510.25741 section 3, as
benchmark/configs/ouro_2_6b_lm_l8.json states and assumes it). ONE stack of
L blocks, applied T times over the same weights:

- layer l, four norm scales: ``a = x + N2_l(Attn_l(N1_l(x)))``,
  ``y = a + N4_l(FFN_l(N3_l(a)))`` (N1 ``input_norm``, N2 ``attn_out_norm``,
  N3 ``post_attn_norm``, N4 ``mlp_out_norm``);
- the loop: ``h_0 = E[ids]``; for t = 1..T: ``u = h_{t-1}``; for l = 1..L:
  ``u = Layer_l(u)``; ``h_t = N_f(u)`` (one ``final_norm``; its OUTPUT is
  what pass t + 1 starts from); exit t reads ``h_t``: ``logits_t = W_head
  h_t`` and the gate ``g_t = w_g . h_t + b_g``, one float32 scalar a token;
- exit distribution and loss: losses.py ``looped_lm_xent``, which takes
  the ``LoopExits`` this model returns in place of logits.

The tree holds L layers' leaves: a weight's gradient is the sum over its T
uses, and ``remat`` checkpoints each of the T x L block applications.
"""

from __future__ import annotations

import math
import sys
from functools import partial

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.ops.attention import (
    ContextParallelConfig,
    dot_product_attention,
)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        dtype = x.dtype
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(dtype)


@flax.struct.dataclass
class LoopExits:
    """What the looped decoder hands its loss in place of logits: the T
    exits' head operands and gate rows (losses.py ``looped_lm_xent``)."""

    x: jax.Array      # (T, B, S, C): h_1..h_T, the final norm's outputs
    table: jax.Array  # (V, C): the untied head, as ops/lm_head.py reads it
    gates: jax.Array  # (T, B, S) float32: g_t, before the sigmoid
    cp: object = flax.struct.field(pytree_node=False)    # the mesh's axes
    beta: float = flax.struct.field(pytree_node=False)   # entropy's weight


def rope_frequencies(head_dim: int, max_seq_len: int, theta: float,
                     scaling: float = 1.0,
                     scaling_type: str = "linear", *,
                     original_max_len: int = 0, beta_fast: float = 32.0,
                     beta_slow: float = 1.0,
                     attention_factor: float = 0.0) -> tuple:
    """Precompute cos/sin tables (S, head_dim/2) in fp32. ``head_dim`` is
    the ROTATED width: below the head's own, :func:`apply_rope` rotates
    the head's first dims and passes the rest (``partial_rotary_factor``).

    ``scaling`` > 1 stretches the usable context to scaling x the
    pretrain length, three recipes (HF rope_scaling types):
    - "linear" (Chen et al. 2023): positions divide by the factor —
      rope(t, scaling=k) == rope(t/k) exactly; uniform compression.
    - "ntk" (NTK-aware, bloc97 2023 / HF "dynamic" at fixed factor):
      the BASE rescales (theta' = theta * k^(D/(D-2))) so the lowest
      frequencies stretch ~k x while the highest (local-order
      resolution) stay nearly untouched — often usable without any
      fine-tuning, unlike linear.
    - "yarn" (Peng et al. 2023, arXiv:2309.00071; HF's
      ``_compute_yarn_parameters``): pair i of frequency f_i keeps f_i
      where it turns more than ``beta_fast`` times over the
      ``original_max_len`` positions of pre-training, takes f_i / k
      where it turns fewer than ``beta_slow`` times, and a linear ramp
      between the two correction dims (floor and ceiling, clamped to
      the rotated width); cos and sin are scaled by
      ``attention_factor`` (0: 0.1 ln k + 1)."""
    if scaling_type not in ("linear", "ntk", "yarn"):
        raise ValueError(
            f"rope_scaling_type must be 'linear', 'ntk' or 'yarn', got "
            f"{scaling_type!r}")
    if scaling_type == "ntk" and scaling != 1.0:
        theta = theta * scaling ** (head_dim / (head_dim - 2))
        scaling = 1.0  # positions stay integral; the base does the work
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling_type == "yarn":
        if original_max_len <= 0:
            raise ValueError("yarn needs rope_original_max_len > 0")

        def correction_dim(turns):  # the pair that turns this often
            return head_dim * math.log(
                original_max_len / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = inv_freq / scaling * ramp + inv_freq * (1.0 - ramp)
        freqs = jnp.outer(jnp.arange(max_seq_len, dtype=jnp.float32),
                          inv_freq)
        factor = attention_factor or 0.1 * math.log(scaling) + 1.0
        return jnp.cos(freqs) * factor, jnp.sin(freqs) * factor
    t = jnp.arange(max_seq_len, dtype=jnp.float32) / scaling
    freqs = jnp.outer(t, inv_freq)  # (S, D/2)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, D). Rotates pairs (x[..., :R/2], x[..., R/2:R]) — the
    'split-half' convention (matches HF Llama, so checkpoints interop) —
    over the first R = 2 x the tables' width dims; dims past R pass."""
    B, S, H, D = x.shape
    R = 2 * cos.shape[-1]
    x1, x2 = x[..., : R // 2], x[..., R // 2: R]
    cos = cos[None, :S, None, :].astype(x.dtype)
    sin = sin[None, :S, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin]
                           + ([x[..., R:]] if R < D else []), axis=-1)


def packed_segments(input_ids: jnp.ndarray, eos_id: int):
    """Document structure of an EOS-packed block, derived at trace time.

    Packed LM blocks (data/text.py: docs joined by EOS, cut to seq_len)
    otherwise let attention leak across document boundaries. Returns
    (segments (B, S) int32 — the 1-based document id of every token (the
    EOS belongs to the document it ends); attention restricts to equal
    ids (ops/attention.py ``segments=``, which builds masks tile-by-tile
    on the chunked path instead of materialising (B, 1, S, S)) — and
    positions (B, S) int32 — each token's offset WITHIN its document, so
    rope/wpe treat every document as starting at position 0, exactly as
    if it were alone in the batch)."""
    B, S = input_ids.shape
    is_eos = input_ids == eos_id
    # token t starts a new segment iff t == 0 or token t-1 was EOS
    is_start = jnp.concatenate(
        [jnp.ones((B, 1), bool), is_eos[:, :-1]], axis=1)
    seg = jnp.cumsum(is_start.astype(jnp.int32), axis=1)  # (B, S), 1-based
    t = jnp.arange(S, dtype=jnp.int32)[None, :]
    starts = jax.lax.cummax(jnp.where(is_start, t, 0), axis=1)
    return seg, t - starts


def apply_rope_rows(x: jnp.ndarray, cos: jnp.ndarray,
                    sin: jnp.ndarray) -> jnp.ndarray:
    """Per-row-position rope: x (B, S, H, D), cos/sin (B, S, D/2) — each
    batch row carries its own position slice (continuous-batching decode,
    serving.py, where slots sit at different sequence offsets)."""
    D = x.shape[-1]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


KV_CACHE_DTYPES = ("", "bfloat16", "float16", "float8_e4m3fn",
                   "float8_e5m2")


def resolve_kv_dtype(kv_cache_dtype: str, default):
    """Validate + resolve the KV-cache storage dtype — ONE rule for every
    model family, erroring with the config key and allowed values instead
    of a numpy dtype error buried in a jit trace."""
    if kv_cache_dtype not in KV_CACHE_DTYPES:
        raise ValueError(
            f"model.kv_cache_dtype must be one of {KV_CACHE_DTYPES}, "
            f"got {kv_cache_dtype!r}")
    return jnp.dtype(kv_cache_dtype) if kv_cache_dtype else default


class LlamaAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    rope_theta: float
    rope_scaling: float
    max_seq_len: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    rope_scaling_type: str = "linear"  # linear | ntk (rope_frequencies)
    cp: ContextParallelConfig | None = None
    attn_impl: str = "auto"  # threaded from ModelConfig.attention_impl
    window: int = 0  # sliding-window attention (0 = full causal)
    quant: str = ""  # "" | "int8" — AQT QAT matmuls (quant.int8_dot_general)
    # KV-cache STORAGE dtype ("" = compute dtype). "float8_e4m3fn" halves
    # cache HBM (and the per-step cache read — decode's bandwidth bill)
    # with a cast at write and read; no scales to manage (the fp8 KV
    # recipe production servers use; e4m3's ±448 range covers rope'd
    # K/V activations). Train-path attention is untouched.
    kv_cache_dtype: str = ""
    # Autoregressive decode: maintain a (B, max_seq_len, H_kv, D) KV cache in
    # the flax 'cache' collection (the idiomatic flax decode pattern — torch
    # analogue: HF past_key_values). Works for both the prefill call (S>1 at
    # offset 0) and single-token steps (S=1 at the running offset).
    decode: bool = False
    # Force the continuation path even for S>1 calls: tokens append at the
    # running cache offset instead of restarting at 0 (speculative
    # decoding's k+1-token verify pass, speculative.py).
    decode_multi: bool = False
    # Continuous batching (serving.py): cache_index is (B,) — every batch
    # row decodes at ITS OWN sequence offset, so serving slots at different
    # positions share one batched step. Prefill still starts rows at 0.
    decode_rows: bool = False
    # PAGED KV cache (serving.PagedContinuousBatcher — the vLLM
    # PagedAttention role, TPU-shaped): K/V live in a FLAT pool of
    # ``paged_blocks`` fixed-size blocks of ``page_size`` tokens,
    # (paged_blocks * page_size, H_kv, D) per layer, and each row maps
    # logical block j -> physical block via the (B, max_blocks)
    # ``block_tables`` argument (host-managed; sentinel ``paged_blocks``
    # marks unallocated entries, whose writes DROP and reads FILL zero —
    # out-of-bounds semantics do the masking, no branches). Resident KV
    # scales with actual sequence lengths instead of B x max_seq_len
    # worst-case rows. decode_rows-only (serving prefills on a dense B=1
    # row model and scatters the range into blocks).
    paged: bool = False
    page_size: int = 0
    paged_blocks: int = 0

    @nn.compact
    def __call__(self, x, segments=None, positions=None,
                 block_tables=None):
        B, S, C = x.shape
        head_dim = C // self.num_heads
        from pytorch_distributed_train_tpu.quant import quant_dot_general

        dg = quant_dot_general(self.quant)
        proj = lambda heads, name: nn.DenseGeneral(  # noqa: E731
            (heads, head_dim), axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, dot_general=dg,
            kernel_init=nn.initializers.normal(0.02), name=name,
        )
        q = proj(self.num_heads, "q_proj")(x)
        k = proj(self.num_kv_heads, "k_proj")(x)
        v = proj(self.num_kv_heads, "v_proj")(x)

        if self.decode and self.paged:
            # Paged KV: flat per-layer pools + host block tables. Only
            # the decode_rows step/continuation shapes exist here —
            # serving prefills on a dense B=1 row model and scatters
            # the range into blocks (serving._paged_scatter_row_range).
            if not self.decode_rows:
                raise ValueError(
                    "paged KV cache requires decode_rows (continuous "
                    "batching); dense decode has no block tables")
            nb, bs = self.paged_blocks, self.page_size
            if nb < 1 or bs < 1:
                raise ValueError(
                    f"paged=True needs page_size >= 1 and paged_blocks "
                    f">= 1, got {bs}, {nb}")
            mb = -(-self.max_seq_len // bs)  # logical blocks per row
            Lp = mb * bs
            cdt = resolve_kv_dtype(self.kv_cache_dtype, k.dtype)
            p_k = self.variable("cache", "pool_key", jnp.zeros,
                                (nb * bs, self.num_kv_heads, head_dim),
                                cdt)
            p_v = self.variable("cache", "pool_value", jnp.zeros,
                                (nb * bs, self.num_kv_heads, head_dim),
                                cdt)
            c_i = self.variable("cache", "cache_index",
                                lambda: jnp.zeros((B,), jnp.int32))
            if S > 1 and not self.decode_multi:
                raise ValueError(
                    "paged prefill is unsupported: prefill on the dense "
                    "row model and scatter the range into blocks")
            tables = (block_tables if block_tables is not None
                      else jnp.full((B, mb), nb, jnp.int32))  # init trace
            idx = c_i.value  # (B,)
            cos, sin = rope_frequencies(head_dim, self.max_seq_len,
                                        self.rope_theta,
                                        self.rope_scaling,
                                        self.rope_scaling_type)
            take = lambda tbl, i: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                tbl, i, S, 0)
            q = apply_rope_rows(q, jax.vmap(take, (None, 0))(cos, idx),
                                jax.vmap(take, (None, 0))(sin, idx))
            k = apply_rope_rows(k, jax.vmap(take, (None, 0))(cos, idx),
                                jax.vmap(take, (None, 0))(sin, idx))
            # Scatter the S new tokens through the block map. Logical
            # block indices clip into the table (gather default);
            # unallocated/dead entries hold the sentinel ``nb`` so their
            # physical index lands out of bounds and the write DROPS —
            # free-running dead rows and re-pinned parked rows stay
            # harmless with zero host branching, the same discipline as
            # the dense cache's masked garbage writes.
            pos = idx[:, None] + jnp.arange(S)  # (B, S)
            # Clamp the FLAT position (the dense path's clamp-to-end
            # discipline): a parked row's free-running index must pile
            # its garbage writes on the single final position Lp-1 —
            # clamping block and offset separately would instead cycle
            # writes through the whole last block, corrupting a parked
            # session's real tail content over time. Lp-1 is always
            # masked (k_pos <= q_pos < L <= Lp never reaches it before
            # a real write does).
            pos_w = jnp.clip(pos, 0, Lp - 1)
            pb = jnp.take_along_axis(tables, pos_w // bs, axis=1)
            phys = pb * bs + pos_w % bs  # (B, S); >= nb*bs if unallocated
            kv_shape = (B * S, self.num_kv_heads, head_dim)
            p_k.value = p_k.value.at[phys.reshape(-1)].set(
                k.astype(cdt).reshape(kv_shape), mode="drop")
            p_v.value = p_v.value.at[phys.reshape(-1)].set(
                v.astype(cdt).reshape(kv_shape), mode="drop")
            c_i.value = idx + S
            # Gather each row's logical view (B, Lp) out of the pool —
            # unallocated blocks read zeros (mode='fill'), and the
            # position mask hides everything past the row's offset
            # anyway. Transient: one (B, Lp, H_kv, D) buffer per layer
            # (freed across layers); RESIDENT KV is just the pool.
            jpos = jnp.arange(Lp)
            physg = (jnp.take(tables, jpos // bs, axis=1) * bs
                     + jpos % bs)  # (B, Lp)
            k_all = jnp.take(p_k.value, physg.reshape(-1), axis=0,
                             mode="fill", fill_value=0).reshape(
                                 B, Lp, self.num_kv_heads, head_dim)
            v_all = jnp.take(p_v.value, physg.reshape(-1), axis=0,
                             mode="fill", fill_value=0).reshape(
                                 B, Lp, self.num_kv_heads, head_dim)
            k_pos = jnp.arange(Lp)
            mask = k_pos[None, None, :] <= pos[:, :, None]  # (B, S, Lp)
            if self.window:
                mask &= (pos[:, :, None] - k_pos[None, None, :]
                         ) < self.window
            y = dot_product_attention(q, k_all.astype(self.dtype),
                                      v_all.astype(self.dtype),
                                      mask=mask[:, None], impl="xla")
        elif self.decode:
            L = self.max_seq_len
            cdt = resolve_kv_dtype(self.kv_cache_dtype, k.dtype)
            c_k = self.variable("cache", "cached_key", jnp.zeros,
                                (B, L, self.num_kv_heads, head_dim), cdt)
            c_v = self.variable("cache", "cached_value", jnp.zeros,
                                (B, L, self.num_kv_heads, head_dim), cdt)
            # decode_rows + decode_multi = MULTI-TOKEN rows continuation
            # (serving.py session resume ingests a whole user turn at each
            # row's offset); plain decode_rows steps are its S=1 case.
            idx_shape = (B,) if self.decode_rows else ()
            c_i = self.variable("cache", "cache_index",
                                lambda: jnp.zeros(idx_shape, jnp.int32))
            if S > 1 and not self.decode_multi:
                # Prefill: a multi-token decode call means "start this cache
                # from position 0" (generate.py's contract). Positions are
                # static, attention is plain causal over the PROMPT ONLY —
                # O(S^2), not O(S*L) over the padded cache — and the
                # configured attn_impl (incl. Pallas) still applies.
                cos, sin = rope_frequencies(head_dim, S, self.rope_theta,
                                             self.rope_scaling,
                                             self.rope_scaling_type)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
                c_k.value = jax.lax.dynamic_update_slice_in_dim(
                    c_k.value, k.astype(cdt), 0, 1)
                c_v.value = jax.lax.dynamic_update_slice_in_dim(
                    c_v.value, v.astype(cdt), 0, 1)
                c_i.value = jnp.full(idx_shape, S, jnp.int32)
                y = dot_product_attention(q, k, v, causal=True,
                                          impl=self.attn_impl,
                                          window=self.window)
            elif self.decode_rows:
                # Per-row continuation: row b's S tokens append at ITS
                # offset idx[b]. vmap turns the per-row dynamic updates
                # into one scatter; positions/mask are per-row too.
                idx = c_i.value  # (B,)
                cos, sin = rope_frequencies(head_dim, L, self.rope_theta,
                                            self.rope_scaling,
                                            self.rope_scaling_type)
                take = lambda tbl, i: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    tbl, i, S, 0)
                cos_r = jax.vmap(take, (None, 0))(cos, idx)
                sin_r = jax.vmap(take, (None, 0))(sin, idx)
                q = apply_rope_rows(q, cos_r, sin_r)
                k = apply_rope_rows(k, cos_r, sin_r)
                upd = lambda c, new, i: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
                    c, new, i, 0)
                c_k.value = jax.vmap(upd)(c_k.value, k.astype(cdt), idx)
                c_v.value = jax.vmap(upd)(c_v.value, v.astype(cdt), idx)
                c_i.value = idx + S
                q_pos = idx[:, None] + jnp.arange(S)  # (B, S)
                k_pos = jnp.arange(L)
                mask = k_pos[None, None, :] <= q_pos[:, :, None]  # (B, S, L)
                if self.window:
                    mask &= (q_pos[:, :, None] - k_pos[None, None, :]
                             ) < self.window
                y = dot_product_attention(q, c_k.value.astype(self.dtype),
                                          c_v.value.astype(self.dtype),
                                          mask=mask[:, None], impl="xla")
            else:
                # Step(s) at the running offset (dynamic index). Handles
                # any static S: with decode_multi this is the multi-token
                # CONTINUATION path (speculative.py's verify pass appends
                # k+1 tokens mid-stream) — positions are idx..idx+S-1 and
                # the mask below is causal across the new tokens too.
                idx = c_i.value
                cos, sin = rope_frequencies(head_dim, L, self.rope_theta,
                                             self.rope_scaling,
                                             self.rope_scaling_type)
                cos = jax.lax.dynamic_slice_in_dim(cos, idx, S, 0)
                sin = jax.lax.dynamic_slice_in_dim(sin, idx, S, 0)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
                c_k.value = jax.lax.dynamic_update_slice_in_dim(
                    c_k.value, k.astype(cdt), idx, 1)
                c_v.value = jax.lax.dynamic_update_slice_in_dim(
                    c_v.value, v.astype(cdt), idx, 1)
                c_i.value = idx + S
                # mask against absolute positions; the unwritten cache tail
                # (> idx) is masked out so the static length leaks nothing
                q_pos = idx + jnp.arange(S)
                k_pos = jnp.arange(L)
                mask = k_pos[None, :] <= q_pos[:, None]
                if self.window:
                    mask &= (q_pos[:, None] - k_pos[None, :]) < self.window
                mask = mask[None, None]
                y = dot_product_attention(q, c_k.value.astype(self.dtype),
                                          c_v.value.astype(self.dtype),
                                          mask=mask, impl="xla")
        else:
            cos, sin = rope_frequencies(head_dim, S, self.rope_theta,
                                             self.rope_scaling,
                                             self.rope_scaling_type)
            if positions is not None:
                # packed segments: each document restarts at position 0
                q = apply_rope_rows(q, cos[positions], sin[positions])
                k = apply_rope_rows(k, cos[positions], sin[positions])
            else:
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)

            y = dot_product_attention(q, k, v, causal=True, cp=self.cp,
                                      impl=self.attn_impl,
                                      window=self.window, segments=segments)
        y = nn.DenseGeneral(
            C, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, dot_general=dg,
            kernel_init=nn.initializers.normal(0.02), name="o_proj",
        )(y)
        return y


class LlamaMLP(nn.Module):
    mlp_dim: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    quant: str = ""  # "" | "int8" (MoE experts always pass "" — fp experts)

    @nn.compact
    def __call__(self, x):
        from pytorch_distributed_train_tpu.quant import quant_dot_general

        dg = quant_dot_general(self.quant)
        dense = lambda dim, name: nn.Dense(  # noqa: E731
            dim, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
            dot_general=dg,
            kernel_init=nn.initializers.normal(0.02), name=name,
        )
        gate = nn.silu(dense(self.mlp_dim, "gate_proj")(x))
        up = dense(self.mlp_dim, "up_proj")(x)
        return dense(x.shape[-1], "down_proj")(gate * up)


class LlamaBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    mlp_dim: int
    rope_theta: float
    rope_scaling: float
    max_seq_len: int
    rms_norm_eps: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    rope_scaling_type: str = "linear"
    cp: ContextParallelConfig | None = None
    moe: "MoeSpec | None" = None  # set → MoE FFN instead of dense (ops/moe.py)
    attn_impl: str = "auto"
    window: int = 0
    quant: str = ""
    kv_cache_dtype: str = ""
    decode: bool = False
    decode_multi: bool = False
    decode_rows: bool = False
    paged: bool = False
    page_size: int = 0
    paged_blocks: int = 0
    # A norm AFTER each sublayer too, on what it adds to the residual
    # stream (four scales a layer; the module docstring's N2 and N4).
    sandwich_norm: bool = False

    @nn.compact
    def __call__(self, x, segments=None, positions=None,
                 block_tables=None):
        after = (lambda name, y: RMSNorm(self.rms_norm_eps, name=name)(y)) \
            if self.sandwich_norm else (lambda name, y: y)
        h = RMSNorm(self.rms_norm_eps, name="input_norm")(x)
        x = x + after("attn_out_norm", LlamaAttention(
            self.num_heads, self.num_kv_heads, self.rope_theta,
            self.rope_scaling, self.max_seq_len, self.dtype,
            self.param_dtype, rope_scaling_type=self.rope_scaling_type,
            cp=self.cp, attn_impl=self.attn_impl,
            window=self.window, quant=self.quant,
            kv_cache_dtype=self.kv_cache_dtype, decode=self.decode,
            decode_multi=self.decode_multi, decode_rows=self.decode_rows,
            paged=self.paged, page_size=self.page_size,
            paged_blocks=self.paged_blocks,
            name="attn",
        )(h, segments=segments, positions=positions,
          block_tables=block_tables))
        h = RMSNorm(self.rms_norm_eps, name="post_attn_norm")(x)
        if self.moe is not None:
            from pytorch_distributed_train_tpu.ops.moe import MoeMLP

            mlp = MoeMLP(self.moe, LlamaMLP, self.mlp_dim, self.dtype,
                         self.param_dtype, name="moe_mlp")
        else:
            mlp = LlamaMLP(self.mlp_dim, self.dtype, self.param_dtype,
                           quant=self.quant, name="mlp")
        x = x + after("mlp_out_norm", mlp(h))
        return x


class LlamaForCausalLM(nn.Module):
    """Input: input_ids (B, S). Output: (B, S, vocab) fp32 logits; with
    ``loop_steps`` > 1 a ``LoopExits`` (the module docstring)."""

    vocab_size: int
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    mlp_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    # Position-interpolation factor: serve/fine-tune at rope_scaling x
    # the pretrain context, by "linear" (positions divide) or "ntk"
    # (base rescales; often usable without fine-tuning) recipe.
    rope_scaling: float = 1.0
    rope_scaling_type: str = "linear"
    rms_norm_eps: float = 1e-5
    remat: bool = True
    remat_policy: str = "full"  # full | dots | dots_no_batch (models/remat.py)
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    cp: ContextParallelConfig | None = None
    moe: "MoeSpec | None" = None
    attn_impl: str = "auto"
    # AQT-style int8 QAT ("" | "int8"): attention + MLP matmuls run
    # int8xint8->int32 on the MXU with dynamic absmax scales and a
    # straight-through backward (quant.int8_dot_general). The lm_head and
    # MoE experts stay in the compute dtype.
    quant_training: str = ""
    # Sliding-window attention span (Mistral recipe; 0 = full causal).
    attention_window: int = 0
    # Packed-block document isolation (packed_segments): >= 0 names the
    # EOS id delimiting documents; attention masks across documents and
    # positions restart per document. -1 = off (documents attend across
    # pack boundaries, the simple-packing default).
    segment_eos_id: int = -1
    decode: bool = False  # KV-cache autoregressive mode (generate.py)
    kv_cache_dtype: str = ""  # "" | fp8 dtypes — cache STORAGE dtype
    # Multi-token continuation in decode mode (speculative.py verify pass)
    decode_multi: bool = False
    # Per-row cache offsets for continuous-batching serving (serving.py)
    decode_rows: bool = False
    # Paged KV pool (serving.PagedContinuousBatcher): block-granular
    # cache residency with host block tables (see LlamaAttention.paged)
    paged: bool = False
    page_size: int = 0
    paged_blocks: int = 0
    # Fused chunked head+CE (losses.chunked_causal_ce): __call__ returns
    # {'loss_sum','weight_sum'} instead of logits — (B,S,V) fp32 logits
    # never materialize. Pair with loss="fused_causal_lm_xent".
    fused_loss: bool = False
    # SP/CP activation anchoring (parallel/mesh.py ActivationSharding):
    # keeps norms/residuals seq-sharded between attention / TP-matmul
    # regions — CP without it replicates seq outside the shard_map regions;
    # SP (Megatron SequenceParallel) IS this constraint.
    act: "object | None" = None
    # The looped decoder (the module docstring): the stack applied
    # loop_steps times over the same weights, a norm after each sublayer,
    # an exit (head and gate) at every pass; loop_entropy_beta weighs the
    # exit distribution's entropy in the loss.
    loop_steps: int = 1
    sandwich_norm: bool = False
    loop_entropy_beta: float = 0.05

    @nn.compact
    def __call__(self, input_ids, train: bool = True, loss_mask=None,
                 block_tables=None):
        del train  # no dropout in the Llama-2 pretrain recipe
        segments = positions = None
        if self.segment_eos_id >= 0:
            if self.decode:
                raise ValueError(
                    "segment_eos_id is a packed-TRAINING feature; decode "
                    "serves one unpacked sequence per row")
            if self.cp is not None and self.cp.active:
                raise ValueError(
                    "segment_eos_id with context parallelism is not "
                    "supported (the segment mask spans the full sequence); "
                    "use context=1 for packed-isolation runs")
            segments, positions = packed_segments(input_ids,
                                                   self.segment_eos_id)
        x = nn.Embed(
            self.vocab_size, self.hidden_size,
            embedding_init=nn.initializers.normal(0.02),
            param_dtype=self.param_dtype, name="tok_embed",
        )(input_ids).astype(self.dtype)
        if self.act is not None:
            x = self.act.constrain(x)

        from pytorch_distributed_train_tpu.models.remat import remat_block

        block_cls = remat_block(LlamaBlock, self.remat, self.remat_policy)

        def make_stack(parent):
            """The L blocks and the final norm, created under ``parent``;
            returns the function that applies them once."""
            blocks = []
            for i in range(self.num_layers):
                moe = (self.moe if self.moe is not None
                       and self.moe.active_for_layer(i) else None)
                blocks.append(block_cls(
                    self.num_heads, self.num_kv_heads, self.mlp_dim,
                    self.rope_theta, self.rope_scaling, self.max_seq_len,
                    self.rms_norm_eps, self.dtype, self.param_dtype,
                    rope_scaling_type=self.rope_scaling_type,
                    cp=self.cp, moe=moe,
                    attn_impl=self.attn_impl, window=self.attention_window,
                    quant=self.quant_training,
                    kv_cache_dtype=self.kv_cache_dtype, decode=self.decode,
                    decode_multi=self.decode_multi,
                    decode_rows=self.decode_rows,
                    paged=self.paged, page_size=self.page_size,
                    paged_blocks=self.paged_blocks,
                    sandwich_norm=self.sandwich_norm,
                    name=f"layer{i}", parent=parent,
                ))
            final_norm = RMSNorm(self.rms_norm_eps, name="final_norm",
                                 parent=parent)

            def stack(x):
                for block in blocks:
                    x = block(x, segments=segments, positions=positions,
                              block_tables=block_tables)
                    if self.act is not None:
                        x = self.act.constrain(x)
                return final_norm(x)

            return stack

        if self.loop_steps > 1:
            return self._loop(make_stack, x)
        x = make_stack(self)(x)
        # Head matmul in the compute dtype with fp32 accumulation: bf16
        # operands hit the MXU at full rate while preferred_element_type
        # keeps the (B,S,V) logits fp32 without an intermediate bf16
        # rounding (an fp32xfp32 matmul here ran at a fraction of MXU rate
        # and the head is ~1/6 of total model FLOPs at 32k vocab).
        head = nn.Dense(
            self.vocab_size, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            dot_general=partial(jax.lax.dot_general,
                                preferred_element_type=jnp.float32),
            kernel_init=nn.initializers.normal(0.02), name="lm_head",
        )
        if self.fused_loss and not self.decode:
            from pytorch_distributed_train_tpu.losses import chunked_causal_ce

            # Create the head params at the standard path without the full
            # matmul (the tiny call is dead code XLA eliminates), then hand
            # the kernel ARRAY to the pure chunked-CE helper — a flax
            # submodule can't be called inside jax.checkpoint, an array can.
            _ = head(x[:, :1])
            kernel = jnp.asarray(head.variables["params"]["kernel"],
                                 self.dtype)
            return chunked_causal_ce(x, kernel, input_ids,
                                     loss_mask=loss_mask)
        logits = head(x)
        return logits.astype(jnp.float32)

    def _loop(self, make_stack, x) -> LoopExits:
        """``loop_steps`` passes of the stack over the same weights, an
        exit after each: the blocks are created once (in the scanned
        pass's body, under this module's own scope), so the tree holds one
        stack's leaves and each leaf's gradient sums over its uses."""
        if self.decode or self.fused_loss or self.moe is not None:
            # a cache entry would be one a (pass, layer) pair: the serving
            # path cannot run the loop yet (ROADMAP)
            raise ValueError(
                "model.loop_steps > 1 is a training-path feature of the "
                "dense decoder: no decode cache, fused_lm_loss or experts")

        # a scan over the passes, the weights broadcast: against the passes
        # unrolled it compiled in 54 s for 155 and ran the step in 516.6 ms
        # for 538.7 (PERF.md section 6, PR 35)
        def one_pass(mdl, x, _):
            with jax.named_scope("loop_pass"):
                x = make_stack(mdl)(x)
            return x, x

        x, hs = nn.scan(one_pass, variable_broadcast="params",
                        split_rngs={"params": False},
                        length=self.loop_steps)(self, x, None)
        gates = nn.Dense(1, dtype=jnp.float32, param_dtype=self.param_dtype,
                         kernel_init=nn.initializers.normal(0.02),
                         name="exit_gate")(hs)[..., 0]
        # the untied head's (C, V) kernel, viewed as the (V, C) table the
        # head's kernels read (ops/lm_head.py); the tiny call creates the
        # leaf at the standard path and is dead code (as the fused head's)
        head = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype,
                        kernel_init=nn.initializers.normal(0.02),
                        name="lm_head")
        _ = head(x[:, :1])
        table = jnp.asarray(head.variables["params"]["kernel"],
                            self.dtype).T
        return LoopExits(hs, table, gates, self.cp, self.loop_entropy_beta)


_loop_logged: set[tuple] = set()


def llama(cfg, dtype, param_dtype, cp=None, act=None) -> LlamaForCausalLM:
    resolve_kv_dtype(getattr(cfg, "kv_cache_dtype", ""), dtype)  # validate NOW
    passes = getattr(cfg, "loop_steps", 1)
    beta = getattr(cfg, "loop_entropy_beta", 0.05)
    sandwich = getattr(cfg, "sandwich_norm", False)
    said = (passes, cfg.num_layers, beta, sandwich)
    if passes > 1 and said not in _loop_logged:  # once a layout, on stderr
        _loop_logged.add(said)
        print(f"[loop] passes={passes} layers={cfg.num_layers} "
              f"applications={passes * cfg.num_layers} exits={passes} "
              f"beta={beta:g} impl=scan sandwich={int(sandwich)}",
              file=sys.stderr, flush=True)
    moe = None
    if getattr(cfg, "num_experts", 0) > 1:
        from pytorch_distributed_train_tpu.ops.moe import MoeSpec

        moe = MoeSpec(
            num_experts=cfg.num_experts,
            top_k=cfg.expert_top_k,
            capacity_factor=cfg.expert_capacity_factor,
            aux_weight=cfg.moe_aux_weight,
            zloss_weight=cfg.moe_zloss_weight,
            every=cfg.moe_every,
            router=cfg.moe_router,
        )
    return LlamaForCausalLM(
        cp=cp,
        moe=moe,
        act=act,
        quant_training=getattr(cfg, "quant_training", ""),
        attn_impl=getattr(cfg, "attention_impl", "auto"),
        attention_window=getattr(cfg, "attention_window", 0),
        kv_cache_dtype=getattr(cfg, "kv_cache_dtype", ""),
        segment_eos_id=getattr(cfg, "segment_eos_id", -1),
        fused_loss=getattr(cfg, "fused_lm_loss", False),
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads or cfg.num_heads,
        mlp_dim=cfg.mlp_dim,
        max_seq_len=cfg.max_seq_len,
        rope_theta=cfg.rope_theta,
        rope_scaling=getattr(cfg, "rope_scaling", 1.0),
        rope_scaling_type=getattr(cfg, "rope_scaling_type", "linear"),
        rms_norm_eps=cfg.rms_norm_eps,
        remat=cfg.remat,
        remat_policy=getattr(cfg, "remat_policy", "full"),
        loop_steps=passes,
        sandwich_norm=sandwich,
        loop_entropy_beta=beta,
        dtype=dtype,
        param_dtype=param_dtype,
    )
