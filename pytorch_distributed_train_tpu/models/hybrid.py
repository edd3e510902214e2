"""A decoder whose layers differ in kind by a pattern (training path).

Each layer's mixer has a kind (``KINDS``, one a layer): ``kda`` is Kimi
Delta Attention (a gated delta rule with a per-channel decay,
arXiv:2510.26692; ``ops/kda.py``), ``mla`` latent attention (DeepSeek-V2
arXiv:2405.04434 section 2.1, expanded form), ``gqa_full`` and
``gqa_window`` grouped-query softmax attention whose query heads, window
and rotation differ by kind, ``conv`` a gated short convolution (LFM2's:
``ConvMixer``). The first ``first_dense_layers`` layers carry a dense
SwiGLU FFN, the rest one chip's share of a routed expert layer, with a
shared expert where the family has one (``ops/moe.py``
``HeldExpertsMLP``). Pre-norm residual blocks, RMSNorm, a head of its own
or the input table's (``tie_word_embeddings``), no dropout, no auxiliary
loss. Two families' language models are laid out so: Ling-3.0-flash (preset
``ling3_flash_lm_ep64``: groups of ``layer_group_size``, the last of each
``mla``, the others ``kda``) and Laguna-S (preset ``laguna_s_lm_ep32``:
``layer_kinds`` a layer, one ``gqa_full`` to three ``gqa_window``); a third,
Solar-Open2 (preset ``solar_open2_lm_ep40_tp8``: one ``gqa_full`` with NO
rotation and a gate a channel to three ``kda`` whose decay gate is the
report's unbounded softplus, step sizes up to 2, low-rank gates), is ONE
CHIP'S SHARE of its mixers too: ``heads_held`` query/KDA heads from
``heads_held_first`` on live here (0 = all), with the KV heads the
published grouping gives them, as one chip of a tensor-parallel group holds
them. The held heads' projections, convolution taps, decay and gates, and
the held ROWS of ``o_proj``: what the absent heads would add to a mixer's
output is left out, that partial sum goes on (as the expert layer's does),
and no code stands in for the absent chips or their all-reduce. A fourth
(preset ``kanana2_lm_ep8``) has EVERY layer ``mla`` in DeepSeek-V3's plain
form (``MLAMixer``'s second: no head norms, no gate, the rotated dims in
pairs), shared experts at a width of their own, and the selection bias's
balancing update after each optimizer step (``ops/moe.py``
``balance_routers``). A fifth (preset ``lfm2_8b_a1b_lm_ep4``) has the
``conv`` mixer three layers in four, grouped-query attention with an
RMSNorm over each head's q and k and NO output gate, a 32-wide sigmoid
router with no shared expert beside it, and the head tied to the input
table. The equations are written out beside each module and in
``benchmark/references/<preset>.py``, which share no code with this file.

Only the training path exists: no cache, no decode (a latent entry, a
recurrent state, a window's ring and a convolution's last tokens in one
cache manager are ROADMAP R2/R7's serving halves).
"""

from __future__ import annotations

import dataclasses
import sys
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.models.llama import (
    LlamaMLP,
    RMSNorm,
    apply_rope,
    rope_frequencies,
)
from pytorch_distributed_train_tpu.ops import kda as kda_ops
from pytorch_distributed_train_tpu.ops import kda_inputs
from pytorch_distributed_train_tpu.ops.attention import (
    ContextParallelConfig,
    dot_product_attention,
)
from pytorch_distributed_train_tpu.ops.moe import (
    HeldExpertsMLP,
    HeldExpertsSpec,
    balance_routers,
    router_load_metrics,
)

_INIT = nn.initializers.normal(0.02)
KINDS = ("kda", "mla", "gqa_full", "gqa_window", "conv")
_F32_OUT = partial(jax.lax.dot_general, preferred_element_type=jnp.float32)


def _heads_merged(dot_general=jax.lax.dot_general):
    """``W x`` with the kernel's (H, d) as ONE dimension of the product,
    split again after it. The same numbers; what changes is the layout the
    TPU's compiler gives the result: a product onto (H, d) comes out with
    the SEQUENCE on the lanes, ``[B, S, H, d]{1,3,2,0}``, and a product onto
    (H d) row-major, which is what the kernels of ops/kda_inputs.py read
    and write: no copy on either side of them (PERF.md section 6, PR 38)."""

    def dot(lhs, rhs, dims, precision=None, preferred_element_type=None):
        out = dot_general(lhs, rhs.reshape(rhs.shape[0], -1), dims,
                          precision=precision)
        return out.reshape(*lhs.shape[:-1], *rhs.shape[1:])

    return dot


def _head_gate(x, num_heads, dtype, param_dtype):
    """sigmoid(W_g x): one scalar a head, float32 (B, S, H, 1)."""
    gate = nn.Dense(num_heads, use_bias=False, dtype=dtype,
                    param_dtype=param_dtype, kernel_init=_INIT,
                    dot_general=_F32_OUT, name="g_proj")(x)
    return jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]


def _channel_gate(x, heads, head_dim, rank, dtype, param_dtype):
    """sigmoid(W x) one value a CHANNEL, float32 (B, S, H, d); ``rank`` > 0:
    W = W_up W_down through ``rank`` features (``gc_down`` every chip of a
    tensor-parallel group computes alike, ``gc_proj`` carries the heads)."""
    if rank:
        x = nn.Dense(rank, use_bias=False, dtype=dtype,
                     param_dtype=param_dtype, kernel_init=_INIT,
                     name="gc_down")(x)
    gate = nn.DenseGeneral((heads, head_dim), axis=-1, use_bias=False,
                           dtype=dtype, param_dtype=param_dtype,
                           kernel_init=_INIT, dot_general=_F32_OUT,
                           name="gc_proj")(x)
    return jax.nn.sigmoid(gate.astype(jnp.float32))


def _out_gate(form, x, heads, head_dim, rank, dtype, param_dtype):
    if form == "channel":
        return _channel_gate(x, heads, head_dim, rank, dtype, param_dtype)
    if form != "head":
        raise ValueError(f"unknown output gate {form!r}; have head | channel")
    return _head_gate(x, heads, dtype, param_dtype)


def held_heads(num_heads: int, held: int, first: int) -> int:
    """How many of a layer's ``num_heads`` live here (``held`` 0 = all)."""
    if not held:
        return num_heads
    if not 0 <= first <= first + held <= num_heads:
        raise ValueError(f"heads {first}..{first + held - 1} held of "
                         f"{num_heads}")
    return held


def held_kv_heads(num_heads: int, num_kv_heads: int, held: int,
                  first: int) -> int:
    """The KV heads the published grouping (query head h reads KV head
    h // (num_heads / num_kv_heads)) gives the held query heads: whole
    groups, or part of one."""
    if not held:
        return num_kv_heads
    group = num_heads // num_kv_heads
    lo, hi = first // group, (first + held - 1) // group
    if hi > lo and (first % group or held % group):
        raise ValueError(
            f"held heads {first}..{first + held - 1} straddle KV heads "
            f"{lo}..{hi} of groups of {group}: a chip holds whole groups, "
            f"or heads of one")
    return hi - lo + 1


def _softplus_gate_init(key, shape, dtype):
    """dt_bias of the unbounded gate, the public fla layer's rule: the step
    softplus(dt_bias) log-uniform in (1e-3, 0.1)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class KDAMixer(nn.Module):
    """q, k, v = W x through a causal depthwise conv and SiLU; q and k
    L2-normalised a head, q scaled by d^-1/2; a log-decay g a key channel;
    a step size beta a head; the delta-rule recurrence; RMSNorm over each
    head's output, times a sigmoid gate; W_o. No rotary. The variants are
    the families', plain fields:

    * ``gate`` ``bounded``: g = lower_bound * sigmoid(exp(A_log) (a +
      dt_bias)); ``softplus``: g = -exp(A_log) softplus(a + dt_bias),
      unbounded below (the report's own). a = W_a x, or with ``gate_rank``
      r > 0 the low-rank W_a2 (W_a1 x) (``a_down``, ``a_proj``).
    * beta = ``beta_scale`` * sigmoid(W_beta x): 1, or 2 where the
      transition may have a negative eigenvalue.
    * ``out_gate`` ``head``: one scalar a head; ``channel``: one a channel,
      through ``gate_rank`` like a.
    * ``heads_held`` of the ``num_heads`` from ``heads_held_first`` on live
      here (0 = all): every leaf with a head dimension carries the held
      heads alone, ``o_proj`` their rows.

    What shapes the projections into q, k, v, g is ops/kda_inputs.py: where
    the core runs in its kernels a kernel pair on the same rows, else XLA.
    Returns (the mixer's output, stats): stats is None for a bounded gate
    (its extremes are the gate's own) and for the unbounded one float32
    (2,): the step's most negative one-token log-decay and largest beta."""

    num_heads: int
    head_dim: int
    conv_kernel_size: int
    gate_lower_bound: float
    rms_norm_eps: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    cp: ContextParallelConfig | None = None
    gate: str = "bounded"
    beta_scale: float = 1.0
    gate_rank: int = 0
    out_gate: str = "head"
    heads_held: int = 0
    heads_held_first: int = 0

    @nn.compact
    def __call__(self, x):
        d, f32 = self.head_dim, jnp.float32
        H = held_heads(self.num_heads, self.heads_held, self.heads_held_first)
        if self.gate not in ("bounded", "softplus"):
            raise ValueError(f"unknown decay gate {self.gate!r}; have "
                             "bounded | softplus")
        unbounded = self.gate == "softplus"
        lower_bound = None if unbounded else self.gate_lower_bound
        # where the shaping and the core run in their kernels (one gate for
        # both), the projections hand them rows of whole heads
        in_kernels = kda_ops.unsupported(x.shape[1], d, d, self.dtype,
                                         self.cp) is None
        proj = lambda name, dot=jax.lax.dot_general, x=x: nn.DenseGeneral(  # noqa: E731
            (H, d), axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=_INIT, name=name,
            dot_general=_heads_merged(dot) if in_kernels else dot)(x)

        taps = [self.param(name, _INIT, (self.conv_kernel_size, H, d),
                           self.param_dtype)
                for name in ("q_conv", "k_conv", "v_conv")]
        if unbounded:
            # the public fla layer's rule: exp(A_log) uniform in (1, 16),
            # the step softplus(dt_bias) log-uniform in (1e-3, 0.1): decays
            # of exp(-0.001) to exp(-1.6) a token at the start
            a_log = self.param(
                "A_log", lambda key, shape, dtype: jnp.log(jax.random.uniform(
                    key, shape, dtype, 1.0, 16.0)), (H,), f32)
            dt_bias = self.param("dt_bias", _softplus_gate_init, (H, d), f32)
        else:
            # exp(A_log) = 1 and dt_bias in (-5, -1) start the channels at
            # decays of exp(-0.03) to exp(-1.3) a token: memories of 1 to 30
            a_log = self.param("A_log", nn.initializers.zeros, (H,), f32)
            dt_bias = self.param(
                "dt_bias", lambda key, shape, dtype: jax.random.uniform(
                    key, shape, dtype, -5.0, -1.0), (H, d), f32)
        a_in = x
        if self.gate_rank:
            a_in = nn.Dense(self.gate_rank, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, kernel_init=_INIT,
                            name="a_down")(x)

        q, k, v, g = kda_inputs.shape_inputs(
            proj("q_proj"), proj("k_proj"), proj("v_proj"),
            proj("a_proj", _F32_OUT, a_in), taps, a_log, dt_bias,
            lower_bound=lower_bound, cp=self.cp)
        beta = jax.nn.sigmoid(nn.Dense(
            H, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=_INIT, dot_general=_F32_OUT,
            name="beta_proj")(x).astype(f32))
        if self.beta_scale != 1.0:
            beta = self.beta_scale * beta
        o = kda_ops.kda_chunked(
            q, k, v, g, beta, chunk=min(kda_ops.DEFAULT_CHUNK, x.shape[1]),
            lower_bound=lower_bound, cp=self.cp)
        o = RMSNorm(self.rms_norm_eps, name="o_norm")(o.astype(f32))
        o = o * _out_gate(self.out_gate, x, H, d, self.gate_rank, self.dtype,
                          self.param_dtype)
        stats = jnp.stack([jnp.min(g), jnp.max(beta)]).astype(f32) \
            if unbounded else None
        return nn.DenseGeneral(
            x.shape[-1], axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=_INIT,
            name="o_proj")(o.astype(self.dtype)), stats


class MLAMixer(nn.Module):
    """Latent attention, expanded (no query compression): q = W_q x, a head
    [nope | rope]; c = RMSNorm(W_dkv x); k_rope = W_kr x, one for all heads;
    [k_nope | v] a head = W_ukv c; RoPE on the rope parts; causal softmax
    attention with scores over sqrt(nope + rope); W_o. Two families' forms,
    by plain fields:

    * ``qk_norm`` True, ``out_gate`` ``head``, ``rope`` ``halves`` (the
      first hybrid preset's): RMSNorm over each head's whole q and whole
      k = [k_nope | k_rope] before the rotation, the rotated dims paired
      (i, i + rope/2), the heads' outputs times a head-wise sigmoid gate.
    * ``qk_norm`` False, ``out_gate`` ``none``, ``rope`` ``pairs``
      (DeepSeek-V3's plain form): no norm over a head, no gate, the rotated
      dims paired (2i, 2i+1), and k_rope rotated ONCE for all heads. The
      pairs are brought side by side first (dims 0, 2, 4, ... then 1, 3,
      5, ...) and rotated as halves, as the family's public code does it: q
      and k are permuted alike, so the scores are those of the pairs
      rotated in place."""

    num_heads: int
    head_dim: int        # nope part of q and k, and v
    rope_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    max_seq_len: int
    rms_norm_eps: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    cp: ContextParallelConfig | None = None
    attn_impl: str = "auto"
    qk_norm: bool = True
    out_gate: str = "head"   # | none
    rope: str = "halves"     # | pairs

    @nn.compact
    def __call__(self, x):
        H, dn, dr = self.num_heads, self.head_dim, self.rope_head_dim
        B, S, _ = x.shape
        if self.rope not in ("halves", "pairs") \
                or self.out_gate not in ("head", "none"):
            raise ValueError(
                f"latent attention: rope {self.rope!r} (have halves | "
                f"pairs), out_gate {self.out_gate!r} (have head | none)")
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=_INIT, name=name)
        norm = lambda name: RMSNorm(self.rms_norm_eps, name=name)  # noqa: E731
        q = dense((H, dn + dr), "q_proj")(x)
        c = norm("kv_norm")(dense(self.kv_lora_rank, "kv_down")(x))
        k_rope = dense(dr, "k_rope_proj")(x)
        kv = dense((H, 2 * dn), "kv_up")(c)
        # (2i, 2i+1) side by side: the halves' rotation then turns the pairs
        paired = (lambda t: jnp.concatenate(  # noqa: E731
            [t[..., 0::2], t[..., 1::2]], -1)) \
            if self.rope == "pairs" else (lambda t: t)

        def rotation():
            cos, sin = rope_frequencies(dr, self.max_seq_len,
                                        self.rope_theta)
            return lambda t: apply_rope(paired(t), cos, sin)

        if self.qk_norm:
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))], -1)
            q, k = norm("q_norm")(q), norm("k_norm")(k)
            turn = rotation()
            rotate = lambda t: jnp.concatenate(  # noqa: E731
                [t[..., :dn], turn(t[..., dn:])], -1)
            q, k = rotate(q), rotate(k)
        else:  # nothing stands between k_rope and its rotation: once
            turn = rotation()
            q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], -1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    turn(k_rope[:, :, None, :]), (B, S, H, dr))], -1)
        y = dot_product_attention(q, k, kv[..., dn:],
                                  causal=True, cp=self.cp,
                                  impl=self.attn_impl)
        if self.out_gate == "head":
            y = y.astype(jnp.float32) * _head_gate(x, H, self.dtype,
                                                   self.param_dtype)
        return nn.DenseGeneral(
            x.shape[-1], axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=_INIT,
            name="o_proj")(y.astype(self.dtype))


@dataclasses.dataclass(frozen=True)
class Rotation:
    """One kind's rotary tables: ``rope_frequencies``' arguments, the
    rotated width first (below the head's: the first dims rotate, the
    rest pass; 0: no rotation at all, and no tables)."""

    width: int
    theta: float
    scaling: float = 1.0
    scaling_type: str = "linear"
    original_max_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0

    def rotate(self, seq_len: int):
        """The function that rotates a (B, S, H, d) tensor by its positions
        0..S-1; width 0: the identity, and no tables are built."""
        if not self.width:
            return lambda x: x
        cos, sin = self.tables(seq_len)
        return lambda x: apply_rope(x, cos, sin)

    def tables(self, seq_len: int):
        return rope_frequencies(
            self.width, seq_len, self.theta, self.scaling, self.scaling_type,
            original_max_len=self.original_max_len,
            beta_fast=self.beta_fast, beta_slow=self.beta_slow,
            attention_factor=self.attention_factor)


class GQAMixer(nn.Module):
    """Grouped-query softmax attention with an output gate: q = W_q x
    (``num_heads`` x d), k, v = W_k x, W_v x (``num_kv_heads`` x d); query
    head h reads KV head h // (num_heads / num_kv_heads); q and k rotated
    by ``rotation`` (width 0: no rotation); scores q k^T / sqrt(d), softmax
    in float32 over the keys j <= i, and with ``window`` > 0 also
    i - j < window; the output times sigmoid(W_g x), one value a head
    (``out_gate`` ``head``) or a channel (``channel``: its own full-rank
    projection), or as it is (``none``); W_o. ``qk_norm``: an RMSNorm over
    each head's q and each head's k before the rotation, one learned
    vector for q and one for k (``q_norm``, ``k_norm``), shared by the
    heads. No bias. ``heads_held`` query heads from
    ``heads_held_first`` on live here (0 = all) with the KV heads the
    grouping gives them (``held_kv_heads``), and their rows of ``o_proj``."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    rotation: Rotation
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    cp: ContextParallelConfig | None = None
    attn_impl: str = "auto"
    out_gate: str = "head"   # | channel | none
    heads_held: int = 0
    heads_held_first: int = 0
    qk_norm: bool = False
    rms_norm_eps: float = 1e-6   # the head norms'

    @nn.compact
    def __call__(self, x):
        heads = held_heads(self.num_heads, self.heads_held,
                           self.heads_held_first)
        kv_heads = held_kv_heads(self.num_heads, self.num_kv_heads,
                                 self.heads_held, self.heads_held_first)
        proj = lambda heads, name: nn.DenseGeneral(  # noqa: E731
            (heads, self.head_dim), axis=-1, use_bias=False,
            dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=_INIT, name=name)(x)
        normed = (lambda t, name: RMSNorm(  # noqa: E731
            self.rms_norm_eps, name=name)(t)) if self.qk_norm \
            else (lambda t, name: t)
        rotate = self.rotation.rotate(x.shape[1])
        y = dot_product_attention(
            rotate(normed(proj(heads, "q_proj"), "q_norm")),
            rotate(normed(proj(kv_heads, "k_proj"), "k_norm")),
            proj(kv_heads, "v_proj"), causal=True,
            window=self.window, cp=self.cp, impl=self.attn_impl)
        if self.out_gate != "none":
            y = y.astype(jnp.float32) * _out_gate(
                self.out_gate, x, heads, self.head_dim, 0, self.dtype,
                self.param_dtype)
        return nn.DenseGeneral(
            x.shape[-1], axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=_INIT,
            name="o_proj")(y.astype(self.dtype))


def short_conv(bcu, taps, dtype):
    """The conv mixer's elementwise chain on [B | C | u] (.., S, 3D) and
    the taps (K, D): z_t = B_t * u_t; c_t = sum_j w_j * z_{t-(K-1)+j}, z
    zero before the sequence's start (depthwise, causal: w_{K-1} meets the
    current token); C_t * c_t, as ``dtype``. In float32 between its
    bfloat16 ends, under the scope ``short_conv``: what a kernel for it
    would take the place of."""
    with jax.named_scope("short_conv"):
        (K, D), S, f32 = taps.shape, bcu.shape[-2], jnp.float32
        b, c, u = (bcu[..., i * D:(i + 1) * D].astype(f32) for i in range(3))
        z = jnp.pad(b * u, ((0, 0), (K - 1, 0), (0, 0)))
        mixed = sum(taps[j].astype(f32) * z[:, j:j + S] for j in range(K))
        return (c * mixed).astype(dtype)


class ConvMixer(nn.Module):
    """LFM2's gated short convolution: [B | C | u] = W_in x (three D-wide
    parts, in that order); the chain of ``short_conv`` over
    ``kernel_size`` taps a channel (no sequence reads another's tokens);
    y_t = W_out (C_t * c_t). No activation, no norm, no bias. Param tree:
    in_proj/kernel (D, 3D), taps (K, D), out_proj/kernel (D, D)."""

    kernel_size: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        D = x.shape[-1]
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=_INIT, name=name)
        taps = self.param("taps", _INIT, (self.kernel_size, D),
                          self.param_dtype)
        gated = short_conv(dense(3 * D, "in_proj")(x), taps, self.dtype)
        return dense(D, "out_proj")(gated)


@dataclasses.dataclass(frozen=True)
class MixerVariants:
    """What a family's mixers differ in, beyond their kinds (config fields
    of the same names under ``model.``; the defaults are the first two
    presets')."""

    kda_gate: str = "bounded"        # | softplus: unbounded below
    kda_beta_scale: float = 1.0      # 2: negative eigenvalues allowed
    kda_gate_rank: int = 0           # 0: full-rank gates; r: through r
    kda_out_gate: str = "head"       # | channel
    gqa_out_gate: str = "head"       # | channel | none
    gqa_qk_norm: bool = False        # RMSNorm over each head's q and k
    heads_held: int = 0              # query/KDA heads held here, 0 = all
    heads_held_first: int = 0
    mla_qk_norm: bool = True         # RMSNorm over a head's whole q and k
    mla_out_gate: str = "head"       # | none
    mla_rope: str = "halves"         # | pairs: (2i, 2i+1) rotate together

    @property
    def mla_form(self) -> str:
        """The latent mixer's form, as the build line says it."""
        parts = (["normed"] if self.mla_qk_norm else []) \
            + (["gated"] if self.mla_out_gate != "none" else [])
        return f"mla={'+'.join(parts) or 'plain'} rope={self.mla_rope}"


class HybridBlock(nn.Module):
    """x + mixer(norm x), then x + ffn(norm x). Returns (x, moe stats, decay
    stats): the expert layer's four numbers (``HeldExpertsMLP``), zeros for a
    dense layer;
    a KDA layer's extremes where its gate is unbounded (``KDAMixer``), else
    None. ``variants`` are the mixers' plain fields (``MixerVariants``:
    gate forms, the share of heads held here). The mixer's
    module carries its kind's name (``kda``, ``mla``, ``conv``, ``gqa`` for
    ``gqa_full``, ``swa`` for ``gqa_window``): a device trace names the
    attention kernel's events by it."""

    kind: str            # this layer's mixer, one of KINDS
    moe: HeldExpertsSpec | None
    num_heads: int       # this layer's query heads
    head_dim: int
    mlp_dim: int
    moe_mlp_dim: int
    rope_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    max_seq_len: int
    conv_kernel_size: int
    kda_gate_lower_bound: float
    rms_norm_eps: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    cp: ContextParallelConfig | None = None
    attn_impl: str = "auto"
    num_kv_heads: int = 0
    window: int = 0
    rotation: Rotation | None = None   # the gqa kinds'
    variants: MixerVariants = MixerVariants()

    @nn.compact
    def __call__(self, x):
        h = RMSNorm(self.rms_norm_eps, name="input_norm")(x)
        var, decay_stats = self.variants, None
        if self.kind in ("gqa_full", "gqa_window"):
            mixed = GQAMixer(
                self.num_heads, self.num_kv_heads, self.head_dim,
                self.window, self.rotation, self.dtype, self.param_dtype,
                cp=self.cp, attn_impl=self.attn_impl,
                out_gate=var.gqa_out_gate, heads_held=var.heads_held,
                heads_held_first=var.heads_held_first,
                qk_norm=var.gqa_qk_norm, rms_norm_eps=self.rms_norm_eps,
                name="gqa" if self.kind == "gqa_full" else "swa")(h)
        elif self.kind == "conv":
            mixed = ConvMixer(self.conv_kernel_size, self.dtype,
                              self.param_dtype, name="conv")(h)
        elif self.kind == "mla":
            mixed = MLAMixer(
                self.num_heads, self.head_dim, self.rope_head_dim,
                self.kv_lora_rank, self.rope_theta, self.max_seq_len,
                self.rms_norm_eps, self.dtype, self.param_dtype, cp=self.cp,
                attn_impl=self.attn_impl, qk_norm=var.mla_qk_norm,
                out_gate=var.mla_out_gate, rope=var.mla_rope, name="mla")(h)
        else:
            assert self.kind == "kda", self.kind
            mixed, decay_stats = KDAMixer(
                self.num_heads, self.head_dim, self.conv_kernel_size,
                self.kda_gate_lower_bound, self.rms_norm_eps, self.dtype,
                self.param_dtype, cp=self.cp, gate=var.kda_gate,
                beta_scale=var.kda_beta_scale, gate_rank=var.kda_gate_rank,
                out_gate=var.kda_out_gate, heads_held=var.heads_held,
                heads_held_first=var.heads_held_first, name="kda")(h)
        x = x + mixed
        h = RMSNorm(self.rms_norm_eps, name="post_attn_norm")(x)
        if self.moe is None:
            out = LlamaMLP(self.mlp_dim, self.dtype, self.param_dtype,
                           name="mlp")(h)
            stats = jnp.zeros((4,), jnp.float32)
        else:
            out, stats = HeldExpertsMLP(
                self.moe, LlamaMLP, self.moe_mlp_dim, self.dtype,
                self.param_dtype, name="moe")(h)
        return x + out, stats, decay_stats


class HybridLM(nn.Module):
    """Input: (B, S) int ids. Output: (B, S, vocab) float32 logits: a
    head of its own, or with ``tie_word_embeddings`` the input table read
    again (logits = h E^T; no ``lm_head`` leaf, and the table's gradient
    is the sum of its two uses). Sows
    the expert layers' row counts (mean over those layers of the fullest
    and of the mean held expert, sum of the pairs past the bound, mean of
    the grouped product's ``moe_tile_visits_ratio``) into the
    ``step_metrics`` collection, which the train step reports; the pairs
    past the bound also as ``update_invalid``, the name by which the step
    keeps its old state and reports ``update_skipped`` (steps.py). Where
    the KDA layers' decay gate is unbounded, also ``kda_log_decay_min`` (the
    step's most negative one-token log-decay over those layers: how far
    past a bounded gate's -5 the chunk core's unbounded form is asked to
    go) and ``kda_beta_max``. Where the expert layers' selection bias has a
    rate (``moe.bias_rate``), each of them sows its router's load over ALL
    its outputs (the ``router_load`` collection, ``ops/moe.py``) and the
    model offers the step the rule that moves the bias by it after the
    optimizer (``balance_routers``) and its metrics (``router_metrics``:
    ``moe_load_fullest``, ``moe_load_mean``, ``moe_bias_abs_max``)."""

    vocab_size: int
    hidden_size: int
    layer_kinds: tuple[str, ...]   # one of KINDS a layer
    layer_heads: tuple[int, ...]   # query heads a layer
    head_dim: int
    mlp_dim: int
    moe_mlp_dim: int
    first_dense_layers: int
    moe: HeldExpertsSpec | None
    rope_head_dim: int = 64
    kv_lora_rank: int = 512
    rope_theta: float = 10000.0
    max_seq_len: int = 8192
    conv_kernel_size: int = 4
    kda_gate_lower_bound: float = -5.0
    rms_norm_eps: float = 1e-6
    num_kv_heads: int = 0
    window: int = 0                       # gqa_window's
    full_rotation: Rotation | None = None    # gqa_full's
    window_rotation: Rotation | None = None  # gqa_window's
    remat: bool = False
    remat_policy: str = "full"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    cp: ContextParallelConfig | None = None
    attn_impl: str = "auto"
    act: "object | None" = None
    variants: MixerVariants = MixerVariants()
    tie_word_embeddings: bool = False

    @nn.compact
    def __call__(self, input_ids, train: bool = True, loss_mask=None):
        del train, loss_mask  # no dropout; the loss masks outside
        from pytorch_distributed_train_tpu.models.remat import remat_block

        constrain = (lambda t: t) if self.act is None else self.act.constrain
        embed = nn.Embed(
            self.vocab_size, self.hidden_size, embedding_init=_INIT,
            param_dtype=self.param_dtype, name="tok_embed")
        x = constrain(embed(input_ids).astype(self.dtype))
        block_cls = remat_block(HybridBlock, self.remat, self.remat_policy)
        stats, decay_stats = [], []
        for i, kind in enumerate(self.layer_kinds):
            moe = self.moe if i >= self.first_dense_layers else None
            windowed = kind == "gqa_window"
            x, layer_stats, layer_decay = block_cls(
                kind, moe, self.layer_heads[i], self.head_dim,
                self.mlp_dim, self.moe_mlp_dim, self.rope_head_dim,
                self.kv_lora_rank, self.rope_theta, self.max_seq_len,
                self.conv_kernel_size, self.kda_gate_lower_bound,
                self.rms_norm_eps, self.dtype, self.param_dtype,
                cp=self.cp, attn_impl=self.attn_impl,
                num_kv_heads=self.num_kv_heads,
                window=self.window if windowed else 0,
                rotation=self.window_rotation if windowed
                else self.full_rotation, variants=self.variants,
                name=f"layer{i}")(x)
            x = constrain(x)
            if moe is not None:
                stats.append(layer_stats)
            if layer_decay is not None:
                decay_stats.append(layer_decay)
        metrics = []
        if stats:
            fullest, mean, over, visits = jnp.stack(stats).T
            metrics += [("moe_rows_fullest", jnp.mean(fullest)),
                        ("moe_rows_mean", jnp.mean(mean)),
                        ("moe_rows_over_bound", jnp.sum(over)),
                        ("moe_tile_visits_ratio", jnp.mean(visits)),
                        ("update_invalid", jnp.sum(over))]
        if decay_stats:
            lowest, largest = jnp.stack(decay_stats).T
            metrics += [("kda_log_decay_min", jnp.min(lowest)),
                        ("kda_beta_max", jnp.max(largest))]
        for name, value in metrics:
            self.sow("step_metrics", name, value,
                     reduce_fn=lambda _, new: new, init_fn=lambda: 0.0)
        x = RMSNorm(self.rms_norm_eps, name="final_norm")(x)
        with jax.named_scope("lm_head"):  # a phase of the step: steps.py
            if self.tie_word_embeddings:
                logits = _F32_OUT(
                    x, embed.embedding.astype(self.dtype),
                    (((x.ndim - 1,), (1,)), ((), ())))
            else:
                logits = nn.Dense(
                    self.vocab_size, use_bias=False, dtype=self.dtype,
                    param_dtype=self.param_dtype, dot_general=_F32_OUT,
                    kernel_init=_INIT, name="lm_head")(x)
        return logits.astype(jnp.float32)


    def balance_routers(self, params, moved, load):
        """The parameters after the step: ``moved`` (after the optimizer)
        with every router's selection bias taken from ``params`` (before
        it) and moved by the balancing rule on the step's ``load``."""
        return balance_routers(params, moved, load, self.moe.bias_rate)

    router_metrics = staticmethod(router_load_metrics)


def layer_kinds(cfg) -> tuple[str, ...]:
    """Each layer's mixer kind: ``cfg.layer_kinds`` where it is given, else
    groups of ``layer_group_size`` whose last is ``mla``, the others
    ``kda``."""
    if cfg.layer_kinds:
        kinds = tuple(cfg.layer_kinds)
        bad = sorted(set(kinds) - set(KINDS))
        if bad or len(kinds) != cfg.num_layers:
            raise ValueError(
                f"model.layer_kinds must name one of {KINDS} for each of "
                f"the {cfg.num_layers} layers, got {kinds}")
        return kinds
    g = cfg.layer_group_size
    return tuple("mla" if g > 0 and (i + 1) % g == 0 else "kda"
                 for i in range(cfg.num_layers))


def layer_heads(cfg) -> tuple[int, ...]:
    """Each layer's query heads: ``cfg.layer_heads`` where given (an
    override hands them over as strings), else ``num_heads`` everywhere."""
    heads = tuple(int(h) for h in cfg.layer_heads) \
        or (cfg.num_heads,) * cfg.num_layers
    if len(heads) != cfg.num_layers:
        raise ValueError(f"model.layer_heads must give {cfg.num_layers} "
                         f"layers' heads, got {heads}")
    return heads


_built_logged: set[tuple] = set()


def hybrid_lm(cfg, dtype, param_dtype, cp=None, act=None) -> HybridLM:
    moe = None
    if cfg.num_experts > 1:
        moe = HeldExpertsSpec(
            num_experts=cfg.num_experts, top_k=cfg.expert_top_k,
            n_groups=cfg.moe_groups, topk_groups=cfg.moe_topk_groups,
            routed_scale=cfg.moe_routed_scale, score=cfg.moe_score,
            held_first=cfg.experts_held_first, held=cfg.experts_held,
            capacity_factor=cfg.expert_capacity_factor,
            shared_mlp_dim=cfg.moe_shared_mlp_dim,
            bias_rate=cfg.moe_bias_rate)
    kinds, heads = layer_kinds(cfg), layer_heads(cfg)
    head_dim = cfg.head_dim or cfg.hidden_size // cfg.num_heads
    kv_heads = cfg.num_kv_heads or cfg.num_heads
    if "gqa_window" in kinds and cfg.attention_window <= 0:
        raise ValueError("a gqa_window layer needs "
                         "model.attention_window > 0")
    variants = MixerVariants(
        kda_gate=cfg.kda_gate, kda_beta_scale=cfg.kda_beta_scale,
        kda_gate_rank=cfg.kda_gate_rank, kda_out_gate=cfg.kda_out_gate,
        gqa_out_gate=cfg.gqa_out_gate, gqa_qk_norm=cfg.gqa_qk_norm,
        heads_held=cfg.heads_held, heads_held_first=cfg.heads_held_first,
        mla_qk_norm=cfg.mla_qk_norm, mla_out_gate=cfg.mla_out_gate,
        mla_rope=cfg.mla_rope)
    share = ""
    if cfg.heads_held:
        if "mla" in kinds or "conv" in kinds:
            raise ValueError("model.heads_held: a latent (mla) layer has no "
                             "share of heads yet, a conv layer no share of "
                             "channels")
        # every layer's share is well formed, and the line names the first's
        held = [(held_heads(h, cfg.heads_held, cfg.heads_held_first),
                 held_kv_heads(h, kv_heads, cfg.heads_held,
                               cfg.heads_held_first)
                 if kind != "kda" else 0)
                for kind, h in zip(kinds, heads)]
        kv_held = max(kv for _, kv in held)
        share = (f" heads_held={held[0][0]}/{heads[0]}"
                 + (f" kv_held={kv_held}/{kv_heads}" if kv_held else ""))
    tied = cfg.tie_word_embeddings
    said = (kinds, heads, kv_heads, cfg.attention_window, variants, tied)
    if said not in _built_logged:  # once a layout, on stderr
        _built_logged.add(said)
        print(f"[hybrid] layers={len(kinds)} kinds={','.join(kinds)} "
              f"heads={','.join(map(str, heads))} kv_heads={kv_heads} "
              f"window={cfg.attention_window} "
              f"dense_layers={cfg.first_dense_layers}{share}"
              + (f" {variants.mla_form}" if "mla" in kinds else "")
              + (" head=tied" if tied else ""),
              file=sys.stderr, flush=True)
    return HybridLM(
        cp=cp, act=act, moe=moe,
        attn_impl=getattr(cfg, "attention_impl", "auto"),
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        layer_kinds=kinds, layer_heads=heads, head_dim=head_dim,
        mlp_dim=cfg.mlp_dim, moe_mlp_dim=cfg.moe_mlp_dim,
        first_dense_layers=cfg.first_dense_layers,
        rope_head_dim=cfg.rope_head_dim, kv_lora_rank=cfg.kv_lora_rank,
        rope_theta=cfg.rope_theta, max_seq_len=cfg.max_seq_len,
        conv_kernel_size=cfg.conv_kernel_size,
        kda_gate_lower_bound=cfg.kda_gate_lower_bound,
        rms_norm_eps=cfg.rms_norm_eps,
        # the grouped-query kinds' (no other kind reads them)
        num_kv_heads=kv_heads, window=cfg.attention_window,
        variants=variants, tie_word_embeddings=tied,
        full_rotation=Rotation(
            int(head_dim * cfg.partial_rotary_factor), cfg.rope_theta,
            cfg.rope_scaling, cfg.rope_scaling_type,
            cfg.rope_original_max_len, cfg.rope_beta_fast,
            cfg.rope_beta_slow, cfg.rope_attention_factor),
        window_rotation=Rotation(head_dim, cfg.window_rope_theta),
        remat=cfg.remat, remat_policy=getattr(cfg, "remat_policy", "full"),
        dtype=dtype, param_dtype=param_dtype,
    )
