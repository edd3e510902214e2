"""T5 encoder-decoder (model-zoo extension beyond the BASELINE matrix).

The encoder-decoder archetype the zoo's decoder-only (llama/gpt2) and
encoder-only (bert/vit) families don't cover: a bidirectional encoder,
a causal decoder with CROSS-attention over the encoder output, bucketed
RELATIVE position biases instead of absolute/rotary embeddings, and a
shared input embedding table. Numerics follow HF transformers'
`T5ForConditionalGeneration` (v1.0, relu feed-forward) exactly — pinned
by the logits-parity tests against the torch implementation
(tests/test_hf_parity.py) in both head variants: untied (this repo's
training default) and tied+d_model**-0.5-rescaled
(`ModelConfig.tie_word_embeddings`, the published-checkpoint layout).

T5-specific conventions replicated (they bite anyone porting T5):
- attention scores are NOT scaled by 1/sqrt(head_dim) — the original
  checkpoints fold the scale into the weight init;
- the relative-attention-bias table lives in block 0 ONLY (one table for
  the encoder stack, one for the decoder stack) and the computed
  (H, Sq, Sk) bias is shared by every later block;
- T5's LayerNorm is scale-only RMS (no mean subtraction, no bias), with
  the mean-square computed in fp32;
- cross-attention has no position bias.

TPU notes: attention runs as explicit einsums with the additive bias
folded in before a fp32 softmax — XLA fuses bias+mask+softmax into the
score matmul's epilogue. The Pallas flash kernel doesn't carry additive
bias (it would need a bias-tile stream); at T5's typical 512-token
encoder lengths the dense path is MXU-bound anyway.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


# T5's LayerNorm IS llama's RMSNorm (scale-only, fp32 mean-square, no
# mean subtraction) — one implementation in the zoo, eps=1e-6 here.
from pytorch_distributed_train_tpu.models.llama import (
    RMSNorm,
    resolve_kv_dtype,
)  # noqa: E402


def relative_position_bucket(relative_position, bidirectional: bool,
                             num_buckets: int, max_distance: int):
    """HF `_relative_position_bucket`: exact log-spaced bucketing.

    relative_position = key_pos - query_pos, int32 array. Encoder
    (bidirectional) splits buckets by sign; decoder buckets only the
    causal past. Near positions get exact buckets, far positions log-
    spaced up to max_distance."""
    rp = relative_position
    buckets = jnp.zeros_like(rp)
    if bidirectional:
        num_buckets //= 2
        buckets = buckets + (rp > 0).astype(jnp.int32) * num_buckets
        rp = jnp.abs(rp)
    else:
        rp = -jnp.minimum(rp, 0)
    max_exact = num_buckets // 2
    is_small = rp < max_exact
    large = max_exact + (
        jnp.log(rp.astype(jnp.float32) / max_exact + 1e-9)
        / np.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return buckets + jnp.where(is_small, rp, large)


class T5Attention(nn.Module):
    """Self- or cross-attention, T5 numerics (no 1/sqrt(d) scale).

    When ``rel_bias`` this module OWNS the stack's relative-bias table
    and returns the computed bias for reuse by later blocks; callers pass
    ``position_bias`` back in for the biasless blocks."""

    num_heads: int
    rel_bias: bool
    bidirectional: bool
    rel_pos_buckets: int
    rel_pos_max_distance: int
    dropout_rate: float
    deterministic: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, kv=None, mask=None, position_bias=None):
        B, Sq, C = x.shape
        kv = x if kv is None else kv
        Sk = kv.shape[1]
        head_dim = C // self.num_heads
        # T5's scaled init is what makes UNSCALED attention scores sane at
        # step 0: q ~ N(0, (d_model*d_kv)^-0.5), k/v/o ~ N(0, d_model^-0.5)
        # (HF T5PreTrainedModel._init_weights with factor=1).
        q_std = (C * head_dim) ** -0.5
        kv_std = C ** -0.5
        proj = lambda heads, std, name: nn.DenseGeneral(  # noqa: E731
            (heads, head_dim), axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.initializers.normal(std), name=name,
        )
        q = proj(self.num_heads, q_std, "q_proj")(x)    # (B, Sq, H, D)
        k = proj(self.num_heads, kv_std, "k_proj")(kv)  # (B, Sk, H, D)
        v = proj(self.num_heads, kv_std, "v_proj")(kv)
        # T5: unscaled scores (the 1/sqrt(d) lives in the checkpoint init)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        if self.rel_bias:
            # HF inits the bias table at d_model**-0.5 like k/v/o
            table = nn.Embed(
                self.rel_pos_buckets, self.num_heads,
                embedding_init=nn.initializers.normal(C ** -0.5),
                param_dtype=self.param_dtype, name="rel_bias")
            rel = (jnp.arange(Sk)[None, :]
                   - jnp.arange(Sq)[:, None]).astype(jnp.int32)
            buckets = relative_position_bucket(
                rel, self.bidirectional, self.rel_pos_buckets,
                self.rel_pos_max_distance)
            position_bias = jnp.transpose(
                table(buckets), (2, 0, 1))[None]      # (1, H, Sq, Sk)
            position_bias = position_bias.astype(jnp.float32)
        if position_bias is not None:
            scores = scores + position_bias
        if mask is not None:
            scores = jnp.where(mask, scores, jnp.float32(-1e9))
        probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
        # HF T5 drops out the attention PROBABILITIES too, not just the
        # sublayer outputs.
        probs = nn.Dropout(self.dropout_rate)(
            probs, deterministic=self.deterministic)
        y = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        out = nn.DenseGeneral(
            C, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.initializers.normal(kv_std), name="o_proj",
        )(y)
        return out, position_bias


class T5MLP(nn.Module):
    """v1.0 DenseReluDense: wi -> relu -> wo, no biases."""

    mlp_dim: int
    dropout_rate: float
    deterministic: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        # HF scaled init: wi ~ N(0, d_model^-0.5), wo ~ N(0, d_ff^-0.5)
        dense = lambda features, std, name: nn.Dense(  # noqa: E731
            features, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.initializers.normal(std), name=name)
        h = nn.relu(dense(self.mlp_dim, x.shape[-1] ** -0.5, "wi")(x))
        h = nn.Dropout(self.dropout_rate)(h, deterministic=self.deterministic)
        return dense(x.shape[-1], self.mlp_dim ** -0.5, "wo")(h)


class T5DecodeAttention(nn.Module):
    """Single-token decoder SELF-attention with a KV cache (generation
    path, generate.generate_seq2seq). Mirrors llama's decode discipline:
    static (B, L, H, D) buffers + a cache_index (scalar, or (B,) under
    ``decode_rows`` — serving.py's per-row offsets), absolute-position
    masking of the unwritten tail. The block-0 relative-bias table is
    looked up per step for the query's absolute position; later blocks
    receive the computed bias ((1, H, 1, L), or (B, H, 1, L) per-row)."""

    num_heads: int
    rel_bias: bool
    rel_pos_buckets: int
    rel_pos_max_distance: int
    max_len: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    # Per-row cache offsets for continuous batching (serving.py) — same
    # contract as llama/gpt2 decode_rows: cache_index is (B,), and the
    # relative-position bias / mask are computed per row.
    decode_rows: bool = False
    kv_cache_dtype: str = ""  # cache STORAGE dtype (llama.py contract)

    @nn.compact
    def __call__(self, x, position_bias=None):
        B, S, C = x.shape
        assert S == 1, "decode steps are single-token"
        head_dim = C // self.num_heads
        q_std = (C * head_dim) ** -0.5
        kv_std = C ** -0.5
        proj = lambda std, name: nn.DenseGeneral(  # noqa: E731
            (self.num_heads, head_dim), axis=-1, use_bias=False,
            dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=nn.initializers.normal(std), name=name,
        )
        q = proj(q_std, "q_proj")(x)
        k = proj(kv_std, "k_proj")(x)
        v = proj(kv_std, "v_proj")(x)
        L = self.max_len
        cdt = resolve_kv_dtype(self.kv_cache_dtype, k.dtype)
        c_k = self.variable("cache", "cached_key", jnp.zeros,
                            (B, L, self.num_heads, head_dim), cdt)
        c_v = self.variable("cache", "cached_value", jnp.zeros,
                            (B, L, self.num_heads, head_dim), cdt)
        idx_shape = (B,) if self.decode_rows else ()
        c_i = self.variable("cache", "cache_index",
                            lambda: jnp.zeros(idx_shape, jnp.int32))
        idx = c_i.value
        if self.decode_rows:
            upd = lambda c, new, i: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
                c, new, i, 0)
            c_k.value = jax.vmap(upd)(c_k.value, k.astype(cdt), idx)
            c_v.value = jax.vmap(upd)(c_v.value, v.astype(cdt), idx)
        else:
            c_k.value = jax.lax.dynamic_update_slice_in_dim(
                c_k.value, k.astype(cdt), idx, 1)
            c_v.value = jax.lax.dynamic_update_slice_in_dim(
                c_v.value, v.astype(cdt), idx, 1)
        c_i.value = idx + 1
        k_pos = jnp.arange(L)
        if self.rel_bias:
            # HF inits the bias table at d_model**-0.5 like k/v/o
            table = nn.Embed(
                self.rel_pos_buckets, self.num_heads,
                embedding_init=nn.initializers.normal(C ** -0.5),
                param_dtype=self.param_dtype, name="rel_bias")
            if self.decode_rows:
                # (B, L) relative distances — one bias row per slot offset
                rel = (k_pos[None, :] - idx[:, None]).astype(jnp.int32)
                buckets = relative_position_bucket(
                    rel, False, self.rel_pos_buckets,
                    self.rel_pos_max_distance)
                position_bias = jnp.transpose(
                    table(buckets), (0, 2, 1))[:, :, None, :]  # (B,H,1,L)
            else:
                buckets = relative_position_bucket(
                    (k_pos - idx).astype(jnp.int32), False,
                    self.rel_pos_buckets, self.rel_pos_max_distance)
                position_bias = jnp.transpose(
                    table(buckets), (1, 0))[None, :, None, :]  # (1,H,1,L)
            position_bias = position_bias.astype(jnp.float32)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q,
                            c_k.value.astype(self.dtype),
                            preferred_element_type=jnp.float32)
        if position_bias is not None:
            scores = scores + position_bias
        live = (k_pos[None, None, None, :]
                <= (idx[:, None, None, None] if self.decode_rows else idx))
        scores = jnp.where(live, scores, jnp.float32(-1e9))
        probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
        y = jnp.einsum("bhqk,bkhd->bqhd", probs,
                       c_v.value.astype(self.dtype))
        out = nn.DenseGeneral(
            C, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.initializers.normal(kv_std), name="o_proj",
        )(y)
        return out, position_bias


class T5Block(nn.Module):
    num_heads: int
    mlp_dim: int
    rel_bias: bool          # block 0 owns the stack's bias table
    is_decoder: bool
    rel_pos_buckets: int
    rel_pos_max_distance: int
    eps: float
    dropout_rate: float
    deterministic: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, enc=None, self_mask=None, cross_mask=None,
                 position_bias=None):
        drop = lambda h: nn.Dropout(self.dropout_rate)(  # noqa: E731
            h, deterministic=self.deterministic)
        attn = partial(
            T5Attention, self.num_heads,
            rel_pos_buckets=self.rel_pos_buckets,
            rel_pos_max_distance=self.rel_pos_max_distance,
            dropout_rate=self.dropout_rate,
            deterministic=self.deterministic,
            dtype=self.dtype, param_dtype=self.param_dtype)

        h = RMSNorm(self.eps, name="ln_self")(x)
        h, position_bias = attn(
            rel_bias=self.rel_bias, bidirectional=not self.is_decoder,
            name="self_attn",
        )(h, mask=self_mask, position_bias=position_bias)
        x = x + drop(h)
        if self.is_decoder:
            h = RMSNorm(self.eps, name="ln_cross")(x)
            h, _ = attn(rel_bias=False, bidirectional=True,
                        name="cross_attn")(h, kv=enc, mask=cross_mask)
            x = x + drop(h)
        h = RMSNorm(self.eps, name="ln_mlp")(x)
        h = T5MLP(self.mlp_dim, self.dropout_rate, self.deterministic,
                  self.dtype, self.param_dtype, name="mlp")(h)
        return x + drop(h), position_bias


class T5ForConditionalGeneration(nn.Module):
    """Inputs: (input_ids (B,Se), decoder_input_ids (B,Sd)); optional
    encoder ``attention_mask``. Output: (B, Sd, vocab) fp32 logits."""

    vocab_size: int
    hidden_size: int = 512
    num_layers: int = 6          # encoder depth
    decoder_layers: int = 0      # 0 -> = num_layers
    num_heads: int = 8
    mlp_dim: int = 2048
    rel_pos_buckets: int = 32
    rel_pos_max_distance: int = 128
    dropout_rate: float = 0.1
    layer_norm_eps: float = 1e-6
    # v1.0 published checkpoints tie the head to `shared` and rescale the
    # decoder output by d_model**-0.5 before it (HF applies the rescale
    # only when tied); untied is this repo's training default.
    tie_head: bool = False
    # Activation rematerialization per block (models/remat.py policies)
    remat: bool = False
    remat_policy: str = "full"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids, decoder_input_ids, train: bool = True,
                 attention_mask=None, loss_mask=None):
        del loss_mask  # seq2seq loss reads weights from the batch
        det = not train
        n_dec = self.decoder_layers or self.num_layers
        shared = nn.Embed(
            self.vocab_size, self.hidden_size,
            embedding_init=nn.initializers.normal(1.0),
            param_dtype=self.param_dtype, name="shared")
        drop = lambda h: nn.Dropout(self.dropout_rate)(  # noqa: E731
            h, deterministic=det)
        from pytorch_distributed_train_tpu.models.remat import remat_block

        block_cls = remat_block(T5Block, self.remat, self.remat_policy)
        block = partial(
            block_cls, self.num_heads, self.mlp_dim,
            rel_pos_buckets=self.rel_pos_buckets,
            rel_pos_max_distance=self.rel_pos_max_distance,
            eps=self.layer_norm_eps, dropout_rate=self.dropout_rate,
            deterministic=det, dtype=self.dtype,
            param_dtype=self.param_dtype)

        # ---- encoder
        Se = input_ids.shape[1]
        enc_mask = None
        if attention_mask is not None:
            enc_mask = attention_mask[:, None, None, :].astype(bool)
        x = drop(shared(input_ids).astype(self.dtype))
        bias = None
        for i in range(self.num_layers):
            x, bias = block(rel_bias=i == 0, is_decoder=False,
                            name=f"enc_block{i}")(
                x, self_mask=enc_mask, position_bias=bias)
        enc = drop(RMSNorm(self.layer_norm_eps, name="enc_final_norm")(x))

        # ---- decoder
        Sd = decoder_input_ids.shape[1]
        causal = jnp.tril(jnp.ones((Sd, Sd), bool))[None, None]
        cross_mask = enc_mask  # (B,1,1,Se) broadcasts over decoder queries
        y = drop(shared(decoder_input_ids).astype(self.dtype))
        bias = None
        for i in range(n_dec):
            y, bias = block(rel_bias=i == 0, is_decoder=True,
                            name=f"dec_block{i}")(
                y, enc=enc, self_mask=causal, cross_mask=cross_mask,
                position_bias=bias)
        y = drop(RMSNorm(self.layer_norm_eps, name="dec_final_norm")(y))

        if self.tie_head:
            y = y * (self.hidden_size ** -0.5)
            emb = jnp.asarray(shared.embedding, self.dtype)
            logits = jax.lax.dot_general(
                y, emb, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            logits = nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype,
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
                kernel_init=nn.initializers.normal(1.0),  # HF: factor*1.0
                name="lm_head",
            )(y)
        return logits.astype(jnp.float32)


def t5(cfg, dtype, param_dtype, cp=None, act=None) -> T5ForConditionalGeneration:
    """Registry ctor. Encoder-decoder context parallelism is not
    implemented — refuse loudly rather than silently train without the
    ring/Ulysses path the mesh asked for."""
    if cp is not None and cp.active:
        raise ValueError(
            "t5 does not support context parallelism (mesh context>1): "
            "the encoder-decoder attention stack has no ring/Ulysses "
            "routing — use context=1 for t5 runs")
    del act
    return T5ForConditionalGeneration(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers,
        decoder_layers=getattr(cfg, "decoder_layers", 0),
        num_heads=cfg.num_heads,
        mlp_dim=cfg.mlp_dim,
        rel_pos_buckets=getattr(cfg, "rel_pos_buckets", 32),
        rel_pos_max_distance=getattr(cfg, "rel_pos_max_distance", 128),
        dropout_rate=cfg.dropout_rate,
        tie_head=getattr(cfg, "tie_word_embeddings", False),
        remat=getattr(cfg, "remat", False),
        remat_policy=getattr(cfg, "remat_policy", "full"),
        dtype=dtype,
        param_dtype=param_dtype,
    )


class T5DecodeBlock(nn.Module):
    """Decoder block for single-token generation: cached self-attention
    (T5DecodeAttention), cross-attention over the fixed encoder output,
    MLP. Submodule names mirror T5Block's decoder layout exactly, so the
    TRAINING param tree drives decoding unchanged."""

    num_heads: int
    mlp_dim: int
    rel_bias: bool
    rel_pos_buckets: int
    rel_pos_max_distance: int
    eps: float
    max_len: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    decode_rows: bool = False
    kv_cache_dtype: str = ""

    @nn.compact
    def __call__(self, x, enc, enc_mask=None, position_bias=None):
        h = RMSNorm(self.eps, name="ln_self")(x)
        h, position_bias = T5DecodeAttention(
            self.num_heads, rel_bias=self.rel_bias,
            rel_pos_buckets=self.rel_pos_buckets,
            rel_pos_max_distance=self.rel_pos_max_distance,
            max_len=self.max_len, dtype=self.dtype,
            param_dtype=self.param_dtype, decode_rows=self.decode_rows,
            kv_cache_dtype=self.kv_cache_dtype,
            name="self_attn",
        )(h, position_bias=position_bias)
        x = x + h
        h = RMSNorm(self.eps, name="ln_cross")(x)
        # Cross K/V are recomputed from `enc` each step (two (Se,C,inner)
        # matmuls per layer per token) rather than cached — simpler, and
        # at T5 shapes the self-attn weight streaming dominates anyway.
        h, _ = T5Attention(
            self.num_heads, rel_bias=False, bidirectional=True,
            rel_pos_buckets=self.rel_pos_buckets,
            rel_pos_max_distance=self.rel_pos_max_distance,
            dropout_rate=0.0, deterministic=True, dtype=self.dtype,
            param_dtype=self.param_dtype, name="cross_attn",
        )(h, kv=enc, mask=enc_mask)
        x = x + h
        h = RMSNorm(self.eps, name="ln_mlp")(x)
        h = T5MLP(self.mlp_dim, 0.0, True, self.dtype, self.param_dtype,
                  name="mlp")(h)
        return x + h, position_bias


class T5Encoder(nn.Module):
    """Encoder-only forward (generation prefill). Same param names as the
    full model's encoder half."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    mlp_dim: int
    rel_pos_buckets: int
    rel_pos_max_distance: int
    layer_norm_eps: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, input_ids, attention_mask=None):
        shared = nn.Embed(
            self.vocab_size, self.hidden_size,
            embedding_init=nn.initializers.normal(1.0),
            param_dtype=self.param_dtype, name="shared")
        enc_mask = None
        if attention_mask is not None:
            enc_mask = attention_mask[:, None, None, :].astype(bool)
        x = shared(input_ids).astype(self.dtype)
        bias = None
        for i in range(self.num_layers):
            x, bias = T5Block(
                self.num_heads, self.mlp_dim, rel_bias=i == 0,
                is_decoder=False, rel_pos_buckets=self.rel_pos_buckets,
                rel_pos_max_distance=self.rel_pos_max_distance,
                eps=self.layer_norm_eps, dropout_rate=0.0,
                deterministic=True, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"enc_block{i}",
            )(x, self_mask=enc_mask, position_bias=bias)
        return RMSNorm(self.layer_norm_eps, name="enc_final_norm")(x)


class T5DecodeStep(nn.Module):
    """One decoder token against a fixed encoder output, KV cache in the
    flax 'cache' collection. Param names mirror the training model, so
    ``model.apply({'params': train_params, 'cache': cache}, ...)`` works
    directly."""

    vocab_size: int
    hidden_size: int
    decoder_layers: int
    num_heads: int
    mlp_dim: int
    rel_pos_buckets: int
    rel_pos_max_distance: int
    layer_norm_eps: float
    max_decode_len: int
    tie_head: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    decode_rows: bool = False
    kv_cache_dtype: str = ""

    @nn.compact
    def __call__(self, dec_ids, enc, enc_mask=None):
        shared = nn.Embed(
            self.vocab_size, self.hidden_size,
            embedding_init=nn.initializers.normal(1.0),
            param_dtype=self.param_dtype, name="shared")
        mask4 = None
        if enc_mask is not None:
            mask4 = enc_mask[:, None, None, :].astype(bool)
        y = shared(dec_ids).astype(self.dtype)
        bias = None
        for i in range(self.decoder_layers):
            y, bias = T5DecodeBlock(
                self.num_heads, self.mlp_dim, rel_bias=i == 0,
                rel_pos_buckets=self.rel_pos_buckets,
                rel_pos_max_distance=self.rel_pos_max_distance,
                eps=self.layer_norm_eps, max_len=self.max_decode_len,
                dtype=self.dtype, param_dtype=self.param_dtype,
                decode_rows=self.decode_rows,
                kv_cache_dtype=self.kv_cache_dtype,
                name=f"dec_block{i}",
            )(y, enc, enc_mask=mask4, position_bias=bias)
        y = RMSNorm(self.layer_norm_eps, name="dec_final_norm")(y)
        if self.tie_head:
            y = y * (self.hidden_size ** -0.5)
            emb = jnp.asarray(shared.embedding, self.dtype)
            logits = jax.lax.dot_general(
                y, emb, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            logits = nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype,
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
                kernel_init=nn.initializers.normal(1.0),  # HF: factor*1.0
                name="lm_head",
            )(y)
        return logits.astype(jnp.float32)


def t5_encoder(cfg, dtype, param_dtype) -> T5Encoder:
    return T5Encoder(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        mlp_dim=cfg.mlp_dim,
        rel_pos_buckets=getattr(cfg, "rel_pos_buckets", 32),
        rel_pos_max_distance=getattr(cfg, "rel_pos_max_distance", 128),
        layer_norm_eps=1e-6, dtype=dtype, param_dtype=param_dtype)


def t5_decode_step(cfg, dtype, param_dtype, max_decode_len: int,
                   decode_rows: bool = False) -> T5DecodeStep:
    resolve_kv_dtype(getattr(cfg, "kv_cache_dtype", ""), dtype)  # validate
    return T5DecodeStep(
        decode_rows=decode_rows,
        kv_cache_dtype=getattr(cfg, "kv_cache_dtype", ""),
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        decoder_layers=getattr(cfg, "decoder_layers", 0) or cfg.num_layers,
        num_heads=cfg.num_heads, mlp_dim=cfg.mlp_dim,
        rel_pos_buckets=getattr(cfg, "rel_pos_buckets", 32),
        rel_pos_max_distance=getattr(cfg, "rel_pos_max_distance", 128),
        layer_norm_eps=1e-6, max_decode_len=max_decode_len,
        tie_head=getattr(cfg, "tie_word_embeddings", False),
        dtype=dtype, param_dtype=param_dtype)
