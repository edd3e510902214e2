"""GPT-2 decoder (model-zoo extension beyond the BASELINE matrix).

The classic pre-LN decoder: learned token + position embeddings, blocks of
ln_1 → attention → residual, ln_2 → MLP(gelu_tanh) → residual, final LN,
and a TIED lm head (logits = h @ wte^T) — the architecture of the HF/torch
``gpt2`` checkpoints, so weights round-trip through interop
(`to_hf_state_dict(..., "gpt2")`) and logits parity is testable against
``transformers.GPT2LMHeadModel`` (tests/test_hf_parity.py).

TPU notes mirror the other LMs: BSHD attention through ops.attention
(fp32 softmax, backend-dispatched), fp32-accumulated bf16 head matmul,
activations castable to the compute dtype throughout. GELU is the tanh
approximation — GPT-2's ``gelu_new``, unlike BERT/ViT's exact erf.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.models.llama import resolve_kv_dtype
from pytorch_distributed_train_tpu.ops.attention import (
    ContextParallelConfig,
    dot_product_attention,
)


class GPT2Attention(nn.Module):
    num_heads: int
    max_seq_len: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    cp: ContextParallelConfig | None = None
    attn_impl: str = "auto"
    window: int = 0  # sliding-window attention (0 = full causal)
    quant: str = ""  # "" | "int8" (quant.int8_dot_general QAT matmuls)
    kv_cache_dtype: str = ""  # cache STORAGE dtype (llama.py contract)
    decode: bool = False  # KV cache (same contract as llama.py decode)
    # S>1 appends at the running offset instead of prefilling from 0
    # (speculative.py's verify pass — same contract as llama.py)
    decode_multi: bool = False
    # Per-row cache offsets for continuous batching (serving.py) — same
    # contract as llama.py decode_rows: cache_index is (B,)
    decode_rows: bool = False

    @nn.compact
    def __call__(self, x, segments=None):
        from pytorch_distributed_train_tpu.quant import quant_dot_general

        B, S, C = x.shape
        head_dim = C // self.num_heads
        dg = quant_dot_general(self.quant)
        proj = lambda name: nn.DenseGeneral(  # noqa: E731
            (self.num_heads, head_dim), axis=-1, dtype=self.dtype,
            param_dtype=self.param_dtype, dot_general=dg,
            kernel_init=nn.initializers.normal(0.02), name=name,
        )
        q, k, v = proj("q_proj")(x), proj("k_proj")(x), proj("v_proj")(x)
        if self.decode:
            L = self.max_seq_len
            cdt = resolve_kv_dtype(self.kv_cache_dtype, k.dtype)
            c_k = self.variable("cache", "cached_key", jnp.zeros,
                                (B, L, self.num_heads, head_dim), cdt)
            c_v = self.variable("cache", "cached_value", jnp.zeros,
                                (B, L, self.num_heads, head_dim), cdt)
            # decode_rows + decode_multi = MULTI-TOKEN rows continuation
            # (serving.py session resume ingests a whole user turn at each
            # row's offset); plain decode_rows steps are its S=1 case.
            idx_shape = (B,) if self.decode_rows else ()
            c_i = self.variable("cache", "cache_index",
                                lambda: jnp.zeros(idx_shape, jnp.int32))
            if S > 1 and not self.decode_multi:
                # prefill from position 0 (generate.py contract)
                c_k.value = jax.lax.dynamic_update_slice_in_dim(
                    c_k.value, k.astype(cdt), 0, 1)
                c_v.value = jax.lax.dynamic_update_slice_in_dim(
                    c_v.value, v.astype(cdt), 0, 1)
                c_i.value = jnp.full(idx_shape, S, jnp.int32)
                y = dot_product_attention(q, k, v, causal=True,
                                          impl=self.attn_impl,
                                          window=self.window)
            elif self.decode_rows:
                # per-row continuation (cf. llama.py): row b's S tokens
                # append at ITS offset idx[b]; vmap'd updates, per-row mask
                idx = c_i.value  # (B,)
                upd = lambda c, new, i: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
                    c, new, i, 0)
                c_k.value = jax.vmap(upd)(c_k.value, k.astype(cdt), idx)
                c_v.value = jax.vmap(upd)(c_v.value, v.astype(cdt), idx)
                c_i.value = idx + S
                q_pos = idx[:, None] + jnp.arange(S)  # (B, S)
                k_pos = jnp.arange(L)
                mask = k_pos[None, None, :] <= q_pos[:, :, None]
                if self.window:
                    mask &= (q_pos[:, :, None] - k_pos[None, None, :]
                             ) < self.window
                y = dot_product_attention(q, c_k.value.astype(self.dtype),
                                          c_v.value.astype(self.dtype),
                                          mask=mask[:, None], impl="xla")
            else:
                idx = c_i.value
                c_k.value = jax.lax.dynamic_update_slice_in_dim(
                    c_k.value, k.astype(cdt), idx, 1)
                c_v.value = jax.lax.dynamic_update_slice_in_dim(
                    c_v.value, v.astype(cdt), idx, 1)
                c_i.value = idx + S
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
            y = dot_product_attention(q, k, v, causal=True, cp=self.cp,
                                      impl=self.attn_impl,
                                      window=self.window, segments=segments)
        return nn.DenseGeneral(
            C, axis=(-2, -1), dtype=self.dtype, param_dtype=self.param_dtype,
            dot_general=dg,
            kernel_init=nn.initializers.normal(0.02), name="c_proj",
        )(y)


class GPT2Block(nn.Module):
    num_heads: int
    mlp_dim: int
    max_seq_len: int
    dropout_rate: float
    deterministic: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    cp: ContextParallelConfig | None = None
    attn_impl: str = "auto"
    window: int = 0
    quant: str = ""
    kv_cache_dtype: str = ""
    decode: bool = False
    decode_multi: bool = False
    decode_rows: bool = False

    @nn.compact
    def __call__(self, x, segments=None):
        from pytorch_distributed_train_tpu.quant import quant_dot_general

        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=1e-5, dtype=jnp.float32, param_dtype=jnp.float32,
            name=name,
        )
        h = ln("ln_1")(x).astype(self.dtype)
        x = x + nn.Dropout(self.dropout_rate)(
            GPT2Attention(self.num_heads, self.max_seq_len, self.dtype,
                          self.param_dtype, cp=self.cp,
                          attn_impl=self.attn_impl, window=self.window,
                          quant=self.quant,
                          kv_cache_dtype=self.kv_cache_dtype,
                          decode=self.decode,
                          decode_multi=self.decode_multi,
                          decode_rows=self.decode_rows,
                          name="attn")(h, segments=segments),
            deterministic=self.deterministic)
        h = ln("ln_2")(x).astype(self.dtype)
        dg = quant_dot_general(self.quant)
        h = nn.Dense(self.mlp_dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, dot_general=dg,
                     kernel_init=nn.initializers.normal(0.02),
                     name="c_fc")(h)
        h = nn.gelu(h)  # tanh approximation == GPT-2's gelu_new
        h = nn.Dense(x.shape[-1], dtype=self.dtype,
                     param_dtype=self.param_dtype, dot_general=dg,
                     kernel_init=nn.initializers.normal(0.02),
                     name="c_proj")(h)
        return x + nn.Dropout(self.dropout_rate)(
            h, deterministic=self.deterministic)


class GPT2LMHead(nn.Module):
    """Input: (B, S) int ids. Output: (B, S, vocab) fp32 logits."""

    vocab_size: int
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_seq_len: int = 1024
    dropout_rate: float = 0.0
    remat: bool = False
    remat_policy: str = "full"  # full | dots | dots_no_batch (models/remat.py)
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    cp: ContextParallelConfig | None = None
    attn_impl: str = "auto"
    attention_window: int = 0  # sliding window (0 = full causal)
    quant_training: str = ""  # "" | "int8" AQT matmuls (tied head stays fp)
    kv_cache_dtype: str = ""  # cache STORAGE dtype (llama.py contract)
    decode: bool = False  # KV-cache autoregressive mode (generate.py)
    # Multi-token continuation in decode mode (speculative.py verify pass)
    decode_multi: bool = False
    # Per-row cache/position offsets for continuous batching (serving.py)
    decode_rows: bool = False
    # Fused chunked head+CE over the tied embedding (losses.chunked_causal_ce)
    fused_loss: bool = False
    # Packed-block document isolation (see llama.py segment_eos_id)
    segment_eos_id: int = -1
    act: "object | None" = None
    # Ways the batch is split in pure data parallelism with the state
    # replicated (steps.grad_reduce_plan; the trainer sets it, no option
    # does): above 1 the tied table is read through _per_shard_table and
    # its gradient is all-reduced once, not once a use.
    tied_shards: int = 1
    # The training step asks for the head's OPERANDS in place of the logits
    # (steps.make_train_step sets it when its loss is causal_lm_xent and
    # nothing else reads the logits; no option does): the final hidden
    # states and the table, or the per-shard view (ops/lm_head.py).
    head_operands: bool = False

    @nn.compact
    def __call__(self, input_ids, train: bool = True, loss_mask=None):
        deterministic = not train
        B, S = input_ids.shape
        segments = seg_positions = None
        if self.segment_eos_id >= 0:
            if self.decode:
                raise ValueError(
                    "segment_eos_id is a packed-TRAINING feature; decode "
                    "serves one unpacked sequence per row")
            if self.cp is not None and self.cp.active:
                raise ValueError(
                    "segment_eos_id with context parallelism is not "
                    "supported; use context=1 for packed-isolation runs")
            from pytorch_distributed_train_tpu.models.llama import (
                packed_segments,
            )

            segments, seg_positions = packed_segments(input_ids,
                                                      self.segment_eos_id)
        wte = nn.Embed(self.vocab_size, self.hidden_size,
                       embedding_init=nn.initializers.normal(0.02),
                       param_dtype=self.param_dtype, name="wte")
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (self.max_seq_len, self.hidden_size),
                         self.param_dtype)
        pos_shape = (B,) if self.decode_rows else ()
        if self.decode and (S == 1 or self.decode_multi):
            # step(s) at the running offset: single-token decode, or a
            # multi-token continuation (speculative.py verify — positions
            # are the absolute idx..idx+S-1, same as the attention cache).
            # decode_rows: each row slices wpe at ITS own offset.
            p_i = self.variable("cache", "pos_index",
                                lambda: jnp.zeros(pos_shape, jnp.int32))
            if self.decode_rows:
                pos = jax.vmap(
                    lambda i: jax.lax.dynamic_slice_in_dim(wpe, i, S, 0)
                )(p_i.value)  # (B, S, C)
            else:
                pos = jax.lax.dynamic_slice_in_dim(wpe, p_i.value, S, 0)[None]
            p_i.value = p_i.value + S
        else:
            # packed segments: each document's positions restart at 0
            pos = (wpe[seg_positions] if seg_positions is not None
                   else wpe[:S][None])
            if self.decode:
                p_i = self.variable("cache", "pos_index",
                                    lambda: jnp.zeros(pos_shape, jnp.int32))
                p_i.value = jnp.full(pos_shape, S, jnp.int32)
        shards = self.tied_shards
        if (self.cp is None or self.decode or self.fused_loss
                or B % shards):
            shards = 1
        if shards > 1:
            # one view of the tied table a shard of the batch (see
            # _per_shard_table): lookup and head both read it, so their
            # two gradient contributions meet BEFORE the sum over shards
            table = _per_shard_table(wte.embedding, shards, self.cp)
            tok = jax.vmap(lambda t, i: jnp.take(t, i, axis=0))(
                table, input_ids.reshape(shards, B // shards, S))
            x = tok.reshape(B, S, -1) + pos
        else:
            x = wte(input_ids) + pos
        x = nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)
        x = x.astype(self.dtype)
        if self.act is not None:
            x = self.act.constrain(x)

        from pytorch_distributed_train_tpu.models.remat import remat_block

        block_cls = remat_block(GPT2Block, self.remat, self.remat_policy)
        for i in range(self.num_layers):
            x = block_cls(
                self.num_heads, self.mlp_dim, self.max_seq_len,
                self.dropout_rate, deterministic, self.dtype,
                self.param_dtype, cp=self.cp, attn_impl=self.attn_impl,
                window=self.attention_window, quant=self.quant_training,
                kv_cache_dtype=self.kv_cache_dtype,
                decode=self.decode, decode_multi=self.decode_multi,
                decode_rows=self.decode_rows,
                name=f"h{i}",
            )(x, segments=segments)
            if self.act is not None:
                x = self.act.constrain(x)

        x = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32,
                         param_dtype=jnp.float32, name="ln_f")(x)
        # Tied head, bf16 operands with fp32 accumulation (cf. bert.py).
        emb = jnp.asarray(table if shards > 1 else wte.embedding,
                          self.dtype)  # (V, C), or (shards, V, C)
        if self.fused_loss and not self.decode:
            from pytorch_distributed_train_tpu.losses import chunked_causal_ce

            return chunked_causal_ce(x.astype(self.dtype), emb, input_ids,
                                     loss_mask=loss_mask,
                                     transpose_kernel=True)
        from pytorch_distributed_train_tpu.ops import lm_head

        head = lm_head.HeadOperands(x.astype(self.dtype), emb, self.cp)
        if self.head_operands and not self.decode:
            return head
        return lm_head.logits(head)


def _per_shard_table(table, shards: int, cp):
    """(V, C) -> (shards, V, C), split over the batch axes: each device
    keeps its own copy, which costs no communication. What reads it is
    batched over shards, so the gradient arrives as (shards, V, C)
    per-shard partial sums and the broadcast's transpose sums them across
    devices once: ONE all-reduce for the leaf, where the partitioner
    otherwise reduces each use's contribution by itself (the tied head's
    and the lookup's: 154 MB in float32 each for GPT-2 small)."""
    from jax.sharding import NamedSharding, PartitionSpec

    out = jnp.broadcast_to(table[None], (shards, *table.shape))
    return jax.lax.with_sharding_constraint(out, NamedSharding(
        cp.mesh, PartitionSpec(tuple(cp.batch_axes), None, None)))


def gpt2(cfg, dtype, param_dtype, cp=None, act=None) -> GPT2LMHead:
    resolve_kv_dtype(getattr(cfg, "kv_cache_dtype", ""), dtype)  # validate NOW
    return GPT2LMHead(
        cp=cp,
        act=act,
        attn_impl=getattr(cfg, "attention_impl", "auto"),
        attention_window=getattr(cfg, "attention_window", 0),
        kv_cache_dtype=getattr(cfg, "kv_cache_dtype", ""),
        quant_training=getattr(cfg, "quant_training", ""),
        segment_eos_id=getattr(cfg, "segment_eos_id", -1),
        fused_loss=getattr(cfg, "fused_lm_loss", False),
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers,
        num_heads=cfg.num_heads,
        mlp_dim=cfg.mlp_dim,
        max_seq_len=cfg.max_seq_len,
        dropout_rate=cfg.dropout_rate,
        remat=cfg.remat,
        remat_policy=getattr(cfg, "remat_policy", "full"),
        dtype=dtype,
        param_dtype=param_dtype,
    )
