"""Analytic model-FLOPs accounting and MFU (VERDICT r3 ask #2).

The reference genre measures throughput in items/sec; the question "is
this actually fast?" needs MFU — achieved FLOP/s over the chip's peak.
Nothing here traces or compiles: every number is a closed-form walk of
the architecture the configs describe (conv/matmul exact, attention
seq-aware), so the accounting is auditable and runs anywhere (including
on hosts with no device at all).

Conventions (stated once, used everywhere):

- **FLOPs = 2 x MACs** (one multiply + one add), the MLPerf / PaLM-MFU
  convention. Beware: vision-literature "GFLOPs" tables usually count
  MACs — torchvision's "4.09 GFLOPs" ResNet-50 is 4.09 GMACs = 8.2
  GFLOPs under this convention.
- **Model FLOPs, not executed FLOPs**: rematerialisation recompute,
  s2d-stem padding-tap overhead, and fused-head chunking do not change
  the number — MFU measures useful work per second, which is why a remat
  config can never "win" MFU by recomputing more.
- **Training step = 3 x forward** (backward = 2x forward, the standard
  two-matmul cotangent accounting). Elementwise/norm/pool FLOPs are
  omitted (sub-1% next to the matmuls, and not MXU work anyway).
- **Attention is counted un-masked** (full S^2), matching the PaLM MFU
  appendix; a causal model that skips half the score tile gets the
  benefit as higher measured MFU, not a smaller denominator.

Peak table: bf16 systolic-array peak and HBM bandwidth per chip, from
the public TPU spec sheets, keyed by the exact PJRT ``device_kind``.
"""

from __future__ import annotations

import math
from typing import Any

# device_kind -> (bf16 peak TFLOP/s, HBM GB/s) per chip. Source: Google
# Cloud TPU documentation, the "System architecture" page of each
# generation (v5e: 197 / 819, v5p: 459 / 2765, v6e: 918 / 1638,
# v4: 275 / 1228, v3: 123 / 900, v2: 45 / 700). The keys are the strings PJRT reports, with both spellings JAX itself pairs
# up per generation (jax/_src/pallas/mosaic/tpu_info.py). Exact match
# only: a kind that is not listed is an error on a TPU, never another
# chip's rate. Decode is bandwidth-bound, so MBU — bytes moved per second
# over the HBM peak — is its utilization measure, as MFU is training's.
_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v6 lite": (918.0, 1638.0),  # Trillium / v6e
    "TPU v6e": (918.0, 1638.0),
    "TPU v5 lite": (197.0, 819.0),   # v5e
    "TPU v5e": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v5": (459.0, 2765.0),       # v5p's other PJRT spelling
    "TPU v4": (275.0, 1228.0),
    "TPU v3": (123.0, 900.0),
    "TPU v2": (45.0, 700.0),
}


def _peaks(device: Any) -> tuple[float, float] | None:
    """The table row of ``device`` (default jax.devices()[0]). None off-TPU
    (an "MFU" against a host core would be noise); an unlisted TPU kind
    raises, so a utilization number never silently vanishes or borrows
    another chip's peak."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if getattr(device, "platform", "") != "tpu":
        return None
    kind = getattr(device, "device_kind", "")
    try:
        return _PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"TPU device_kind {kind!r} is not in the peaks table "
            f"(utils/flops.py _PEAKS: {sorted(_PEAKS)}); add its published "
            "bf16 peak and HBM bandwidth with their source") from None


def device_hbm_bandwidth(device: Any = None) -> float | None:
    """HBM bytes/sec of ``device`` (default jax.devices()[0]); None off-TPU."""
    row = _peaks(device)
    return None if row is None else row[1] * 1e9


def device_peak_flops(device: Any = None) -> float | None:
    """bf16 peak FLOP/s of ``device`` (default jax.devices()[0]); None
    off-TPU."""
    row = _peaks(device)
    return None if row is None else row[0] * 1e12


# ---------------------------------------------------------------------------
# Vision
# ---------------------------------------------------------------------------


def _conv_out(n: int, k: int, s: int, pad: int) -> int:
    return (n + 2 * pad - k) // s + 1


def resnet_fwd_flops(cfg) -> float:
    """Forward FLOPs/image for models/resnet.py's architecture, walking
    the exact stage/block/stride schedule (stage_sizes from the name).
    The s2d stem counts as the canonical 7x7 conv it computes (model
    FLOPs; the zero-padded taps are execution overhead, not work)."""
    deep = cfg.name == "resnet50"
    stage_sizes = (3, 4, 6, 3) if deep else (2, 2, 2, 2)
    img = cfg.image_size
    cifar_stem = (not deep) and img <= 64
    f0 = 64
    flops = 0.0

    if cifar_stem:
        h = _conv_out(img, 3, 1, 1)
        flops += 2.0 * h * h * f0 * 3 * 3 * 3
        cin = f0
    else:
        h = _conv_out(img, 7, 2, 3)
        flops += 2.0 * h * h * f0 * 7 * 7 * 3
        h = _conv_out(h, 3, 2, 1)  # maxpool
        cin = f0

    for i, blocks in enumerate(stage_sizes):
        f = f0 * 2 ** i
        for j in range(blocks):
            s = 2 if i > 0 and j == 0 else 1
            if deep:
                # 1x1 cin->f, 3x3/s f->f, 1x1 f->4f (+1x1/s proj cin->4f)
                flops += 2.0 * h * h * cin * f
                ho = _conv_out(h, 3, s, 1)
                flops += 2.0 * ho * ho * f * 3 * 3 * f
                flops += 2.0 * ho * ho * f * 4 * f
                if s != 1 or cin != 4 * f:
                    flops += 2.0 * ho * ho * cin * 4 * f
                cin, h = 4 * f, ho
            else:
                ho = _conv_out(h, 3, s, 1)
                flops += 2.0 * ho * ho * cin * 3 * 3 * f
                flops += 2.0 * ho * ho * f * 3 * 3 * f
                if s != 1 or cin != f:
                    flops += 2.0 * ho * ho * cin * f
                cin, h = f, ho

    flops += 2.0 * cin * cfg.num_classes  # fc after global pool
    return flops


def vit_fwd_flops(cfg) -> float:
    """Forward FLOPs/image for models/vit.py (cls token, learned pos)."""
    d, m = cfg.hidden_size, cfg.mlp_dim
    grid = cfg.image_size // cfg.patch_size
    s = grid * grid + 1  # + cls token
    # patch embedding: one matmul per patch, (patch^2 * 3) -> d
    flops = 2.0 * grid * grid * (cfg.patch_size ** 2 * 3) * d
    per_layer = (
        8.0 * s * d * d          # q,k,v,o projections
        + 4.0 * s * s * d        # QK^T and AV
        + 4.0 * s * d * m        # mlp in + out
    )
    flops += cfg.num_layers * per_layer
    flops += 2.0 * d * cfg.num_classes  # head on the cls token
    return flops


# ---------------------------------------------------------------------------
# Transformers (per token, seq-aware)
# ---------------------------------------------------------------------------


def _attn_proj_flops(cfg) -> float:
    """Per-token q/k/v/o projection FLOPs, GQA-aware."""
    d, h = cfg.hidden_size, cfg.num_heads
    hkv = cfg.num_kv_heads or h
    dh = d // h
    return 2.0 * d * d * 2 + 2.0 * d * (dh * hkv) * 2  # q+o, k+v


def llama_fwd_flops_per_token(cfg, seq: int | None = None) -> float:
    """models/llama.py: RMSNorm blocks, GQA, SwiGLU, untied head. The
    looped decoder (``loop_steps`` T > 1) applies its layers T times and
    its head at each of the T exits: both count T times, whatever the
    parameter count says (the exit gate's d products a pass are left out)."""
    s = seq or cfg.max_seq_len
    d, m = cfg.hidden_size, cfg.mlp_dim
    passes = max(getattr(cfg, "loop_steps", 1), 1)
    per_layer = (
        _attn_proj_flops(cfg)
        + 4.0 * s * d       # QK^T + AV (un-masked convention)
        + 6.0 * d * m       # SwiGLU: gate + up + down
    )
    return passes * (cfg.num_layers * per_layer + 2.0 * d * cfg.vocab_size)


def gpt2_fwd_flops_per_token(cfg, seq: int | None = None) -> float:
    """models/gpt2.py: MHA, 2-matmul GELU MLP, tied head (same FLOPs)."""
    s = seq or cfg.max_seq_len
    d, m = cfg.hidden_size, cfg.mlp_dim
    per_layer = 8.0 * d * d + 4.0 * s * d + 4.0 * d * m
    return cfg.num_layers * per_layer + 2.0 * d * cfg.vocab_size


def band_pairs_per_token(seq: int, window: int = 0) -> float:
    """Mean (query, key) pairs a token of a causal band: query i meets
    keys j <= i, and with ``window`` > 0 only i - j < window:
    sum_i min(i + 1, window) / seq."""
    w = min(window, seq) if window > 0 else seq
    return (w * (w + 1) / 2.0 + (seq - w) * w) / seq


def hybrid_fwd_flops_per_token(cfg, seq: int | None = None) -> float:
    """models/hybrid.py, what THIS chip computes a token: the matrices a
    token meets (of the routed experts only its expected share of the held
    ones: top_k x held / num_experts), the latent layers' scores and values
    (un-masked convention, as the other rows here), the grouped-query
    kinds' scores and values over the pairs of their band alone (causal,
    and inside the window: the work done, at each layer's own heads), the
    ``conv`` kind's two projections and its chain (a multiply-add a tap and
    the two gates a channel; no term in the sequence's length), a shared
    expert only where the family has one, the head as ONE product whether
    its matrix is its own or the input table's, and
    the delta rule's chunk products (in-chunk tables and the three products
    with the state), all at the heads held here (``model.heads_held``) and
    with a gate through ``kda_gate_rank`` counted as its two products. The
    Neumann inverse's small products are left out (under 1 %)."""
    from pytorch_distributed_train_tpu.models.hybrid import (
        held_heads,
        held_kv_heads,
        layer_heads,
        layer_kinds,
    )
    from pytorch_distributed_train_tpu.ops.kda import chunk_in_use

    s = seq or cfg.max_seq_len
    d = cfg.hidden_size
    dh = cfg.head_dim or d // cfg.num_heads
    dr, r, c = cfg.rope_head_dim, cfg.kv_lora_rank, chunk_in_use(s, dh, dh)
    hkv = cfg.num_kv_heads or cfg.num_heads
    kinds = layer_kinds(cfg)
    n_dense = min(cfg.first_dense_layers, cfg.num_layers)
    n_moe = cfg.num_layers - n_dense if cfg.num_experts > 1 else 0
    # the heads held HERE (model.heads_held; 0 = all of a layer's)
    here = lambda heads: held_heads(  # noqa: E731
        heads, cfg.heads_held, cfg.heads_held_first)

    def channels(h):  # d -> (h, dh): full rank, or through kda_gate_rank
        rank = cfg.kda_gate_rank
        return 2.0 * d * rank + 2.0 * rank * h * dh if rank \
            else 2.0 * d * h * dh

    def kda(heads):
        h = here(heads)
        gate = channels(h) if cfg.kda_out_gate == "channel" else 2.0 * d * h
        return (2.0 * d * h * dh * 4    # q, k, v, output projections
                + channels(h)           # the decay's
                + 2.0 * d * h + gate    # beta and the output gate
                + 2.0 * h * c * dh * 3  # tables A, B and B U, a token
                + 2.0 * h * dh * dh * 3)  # W S, (Q exp G) S, the update

    def mla(heads):
        h = heads
        gate = 2.0 * d * h if cfg.mla_out_gate == "head" else 0.0
        return (2.0 * d * h * (dh + dr) + 2.0 * d * (r + dr)
                + 2.0 * r * h * 2 * dh + 2.0 * h * dh * d + gate
                + 2.0 * s * h * (dh + dr) + 2.0 * s * h * dh)

    def gqa(heads, window):  # q and o, k and v, the gate; scores and values
        h = here(heads)
        kv = held_kv_heads(heads, hkv, cfg.heads_held, cfg.heads_held_first)
        gate = 0.0 if cfg.gqa_out_gate == "none" else \
            2.0 * d * h * (dh if cfg.gqa_out_gate == "channel" else 1)
        return (4.0 * d * h * dh + 4.0 * d * kv * dh + gate
                + 4.0 * h * dh * band_pairs_per_token(s, window))

    # in_proj (d -> 3d) and out_proj; B * u, the taps, C * a channel
    conv = 8.0 * d * d + (2.0 * cfg.conv_kernel_size + 4.0) * d
    mixers = sum(
        kda(heads) if kind == "kda" else mla(heads) if kind == "mla"
        else conv if kind == "conv"
        else gqa(heads, cfg.attention_window if kind == "gqa_window" else 0)
        for kind, heads in zip(kinds, layer_heads(cfg)))
    expert = 6.0 * d * cfg.moe_mlp_dim
    shared = 0.0 if cfg.moe_shared_mlp_dim < 0 \
        else 6.0 * d * (cfg.moe_shared_mlp_dim or cfg.moe_mlp_dim)
    held = cfg.experts_held or cfg.num_experts
    moe = (2.0 * d * cfg.num_experts + shared
           + expert * cfg.expert_top_k * held / max(cfg.num_experts, 1))
    return (mixers + n_dense * 6.0 * d * cfg.mlp_dim
            + n_moe * moe + 2.0 * d * cfg.vocab_size)


def bert_fwd_flops_per_token(cfg, seq: int | None = None) -> float:
    """models/bert.py: post-LN MHA blocks + MLM head (dense D->D, GELU,
    LN, tied-embedding decode) computed at every position."""
    s = seq or cfg.max_seq_len
    d, m = cfg.hidden_size, cfg.mlp_dim
    per_layer = 8.0 * d * d + 4.0 * s * d + 4.0 * d * m
    head = 2.0 * d * d + 2.0 * d * cfg.vocab_size
    return cfg.num_layers * per_layer + head


def t5_fwd_flops_per_token(cfg, src: int | None = None,
                           tgt: int | None = None) -> float:
    """models/t5.py enc-dec, amortised PER TOKEN over (src + tgt) tokens
    — matching the bench/trainer convention that counts encoder source +
    decoder target tokens as the throughput denominator. DenseReluDense
    (2 matmuls), decoder adds cross-attention over the src length."""
    s_src = src or cfg.max_seq_len
    s_tgt = tgt or max(s_src // 4, 1)
    d, m = cfg.hidden_size, cfg.mlp_dim
    dec_layers = cfg.decoder_layers or cfg.num_layers
    enc_layer = 8.0 * d * d + 4.0 * s_src * d + 4.0 * d * m
    dec_layer = (
        8.0 * d * d + 4.0 * s_tgt * d       # self-attention
        + 8.0 * d * d + 4.0 * s_src * d     # cross-attention (q from tgt)
        + 4.0 * d * m
    )
    enc_total = cfg.num_layers * enc_layer * s_src
    dec_total = dec_layers * dec_layer * s_tgt
    head_total = 2.0 * d * cfg.vocab_size * s_tgt
    return (enc_total + dec_total + head_total) / (s_src + s_tgt)


# ---------------------------------------------------------------------------
# Dispatch + MFU
# ---------------------------------------------------------------------------

# model name -> (fn(cfg, seq) -> fwd FLOPs per ITEM, item noun). The item
# matches the throughput unit bench.py / the trainer report: images for
# vision, tokens for LMs (t5: source+target tokens).
_FWD = {
    "resnet18": (lambda cfg, seq: resnet_fwd_flops(cfg), "image"),
    "resnet50": (lambda cfg, seq: resnet_fwd_flops(cfg), "image"),
    "vit_b16": (lambda cfg, seq: vit_fwd_flops(cfg), "image"),
    "llama": (llama_fwd_flops_per_token, "token"),
    "llama_pp": (llama_fwd_flops_per_token, "token"),
    "gpt2": (gpt2_fwd_flops_per_token, "token"),
    "hybrid_lm": (hybrid_fwd_flops_per_token, "token"),
    "bert_base": (bert_fwd_flops_per_token, "token"),
    "t5": (lambda cfg, seq: t5_fwd_flops_per_token(cfg, seq), "token"),
}


def fwd_flops_per_item(model_cfg, seq: int | None = None) -> float | None:
    """Forward FLOPs per throughput item (image or token), or None for
    models without an accounting entry."""
    entry = _FWD.get(model_cfg.name)
    if entry is None:
        return None
    return entry[0](model_cfg, seq)


def train_flops_per_item(model_cfg, seq: int | None = None) -> float | None:
    """fwd + bwd FLOPs per item for one training step (3 x forward)."""
    fwd = fwd_flops_per_item(model_cfg, seq)
    return None if fwd is None else 3.0 * fwd


def aot_fwd_flops_per_item(model_cfg, precision_cfg=None, *,
                           seq: int | None = None,
                           batch: int = 1) -> float | None:
    """XLA's own forward FLOP count per item, from jax AOT
    ``lower(...).cost_analysis()`` — the independent cross-check that
    keeps the hand-rolled formulas above from silently drifting when a
    model changes (tests compare this against ``fwd_flops_per_item``
    within tolerance). HLO-level only: lowering, no backend compile, so
    it runs in seconds on the CPU test backend. Returns None when the
    model has no throughput-item convention here (unlisted name) or the
    lowering exposes no flops estimate.

    The item denominator matches ``fwd_flops_per_item``: images for
    vision models, tokens for LMs (batch * seq tokens per forward).
    """
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu.config import PrecisionConfig
    from pytorch_distributed_train_tpu.models.registry import build_model

    entry = _FWD.get(model_cfg.name)
    if entry is None or model_cfg.name == "t5":
        # t5's per-token amortisation spans two sequences (src + tgt);
        # the single-input lowering here doesn't model it.
        return None
    if precision_cfg is None:
        # fp32 lowering: cost_analysis counts the same dot/conv flops
        # regardless, and fp32 avoids backend-specific bf16 expansions.
        precision_cfg = PrecisionConfig(compute_dtype="float32")
    model = build_model(model_cfg, precision_cfg)
    noun = entry[1]
    if noun == "image":
        x = jnp.zeros((batch, model_cfg.image_size, model_cfg.image_size,
                       3), jnp.float32)
        items = batch
    else:
        s = seq or model_cfg.max_seq_len
        x = jnp.zeros((batch, s), jnp.int32)
        items = batch * s

    def fwd(params, inputs):
        return model.apply(params, inputs, train=False)

    params = jax.eval_shape(
        lambda r: model.init({"params": r}, x, train=False),
        jax.random.PRNGKey(0))
    x_shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
    try:
        cost = jax.jit(fwd).lower(params, x_shape).cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):  # some backends wrap per-device
        cost = cost[0] if cost else {}
    flops = (cost or {}).get("flops")
    if not flops or flops <= 0:
        return None
    return float(flops) / items


def llama_param_count(cfg) -> float:
    """Exact parameter count for models/llama.py's architecture (GQA,
    SwiGLU, untied head; norms counted — they read like everything else)."""
    d, m, h = cfg.hidden_size, cfg.mlp_dim, cfg.num_heads
    hkv = cfg.num_kv_heads or h
    dh = d // h
    per_layer = (
        d * d + d * d                 # q_proj + o_proj
        + 2 * d * (hkv * dh)          # k_proj + v_proj
        + 3 * d * m                   # SwiGLU gate/up/down
        # two RMSNorm scales, four under sandwich_norm
        + (4 if getattr(cfg, "sandwich_norm", False) else 2) * d
    )
    looped = getattr(cfg, "loop_steps", 1) > 1
    return (cfg.num_layers * per_layer  # ONE stack, however often applied
            + 2 * cfg.vocab_size * d  # embedding + untied head
            + d                       # final norm
            + (d + 1 if looped else 0))  # the exit gate and its bias


def decode_bytes_per_token(cfg, *, batch: int, avg_position: float,
                           weight_bytes_per_param: float = 2.0,
                           kv_bytes_per_elt: float = 2.0) -> float:
    """HBM bytes a llama-family model must MOVE per generated token: the
    full weight read amortized over the batch (every row shares one pass)
    plus the row's own K/V cache read at ``avg_position`` fill. This is
    the decode-side roofline denominator — tokens/sec x this, over the
    chip's HBM bandwidth, is MBU. Weight/kv byte sizes parameterize the
    quantization levers (int8 = 1, int4 = 0.5, fp8 kv = 1)."""
    d, h = cfg.hidden_size, cfg.num_heads
    hkv = cfg.num_kv_heads or h
    dh = d // h
    weights = llama_param_count(cfg) * weight_bytes_per_param / max(batch, 1)
    kv_read = 2.0 * cfg.num_layers * hkv * dh * avg_position \
        * kv_bytes_per_elt
    return weights + kv_read


def mbu_pct(tokens_per_sec_per_chip: float, bytes_per_token: float | None,
            bandwidth: float | None) -> float | None:
    """Model-bandwidth utilization %: moved bytes/sec over HBM peak."""
    if not bytes_per_token or not bandwidth:
        return None
    if not math.isfinite(tokens_per_sec_per_chip):
        return None
    return 100.0 * tokens_per_sec_per_chip * bytes_per_token / bandwidth


def mfu_pct(items_per_sec_per_chip: float, flops_per_item: float | None,
            peak_flops: float | None) -> float | None:
    """Achieved / peak FLOP/s as a percentage; None when either side of
    the ratio is unknown (no accounting entry, or a CPU backend)."""
    if not flops_per_item or not peak_flops:
        return None
    if not math.isfinite(items_per_sec_per_chip):
        return None
    return 100.0 * items_per_sec_per_chip * flops_per_item / peak_flops
