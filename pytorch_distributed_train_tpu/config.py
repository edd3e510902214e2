"""Config system: one dataclass tree + named presets + CLI overrides.

Replaces the reference harness's argparse CLI + launcher env contract
(SURVEY.md §5.6; BASELINE.json:5 "behind the same config ... interface").
The five BASELINE.json configs (lines 7-11) ship as named presets — they are
the acceptance matrix:

    resnet18_cifar10   ResNet-18 / CIFAR-10, single process       (line 7)
    resnet50_imagenet  ResNet-50 / ImageNet, data-parallel        (line 8)
    vit_b16_imagenet   ViT-B/16, bf16 + grad accumulation         (line 9)
    bert_base_mlm      BERT-base MLM, LAMB optimizer              (line 10)
    llama2_7b          Llama-2 7B pretrain, GSPMD param sharding  (line 11)

Parallelism is *config*, not code: the ``mesh`` section chooses axis sizes on
``('data','fsdp','tensor','context')`` and the partition rules in
parallel/partition.py do the rest (SURVEY.md §7.2).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any


def _fields(cls) -> dict[str, dataclasses.Field]:
    return {f.name: f for f in dataclasses.fields(cls)}


@dataclass
class ModelConfig:
    """Which model to build and its architecture knobs.

    ``name`` keys into models/registry.py. Transformer fields are ignored by
    the vision models and vice versa.
    """

    name: str = "resnet18"
    num_classes: int = 10
    image_size: int = 32
    # ResNet ImageNet stem: "conv" (7x7/s2, torch-identical) or
    # "space_to_depth" (mathematically-exact 4x4/s1 rewrite over a 2x2
    # space-to-depth input — MXU-friendly C_in 3→12; the parameter keeps
    # the canonical (7,7,3,F) layout so checkpoints/interop are unchanged)
    stem: str = "conv"
    # ViT
    patch_size: int = 16
    # Transformer family (ViT / BERT / Llama)
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 0  # 0 → = num_heads (MHA); <num_heads → GQA (Llama)
    mlp_dim: int = 3072
    vocab_size: int = 30522
    max_seq_len: int = 512
    dropout_rate: float = 0.0
    # Llama
    rope_theta: float = 10000.0
    # Linear RoPE position interpolation (HF rope_scaling "linear"): >1
    # stretches the usable context to rope_scaling x the pretrain length
    # (set max_seq_len accordingly; positions divide by the factor).
    rope_scaling: float = 1.0
    # "linear" (positions divide by the factor; fine-tune for quality),
    # "ntk" (base rescales, high frequencies preserved; often works
    # zero-shot) or "yarn" (hybrid_lm's full-attention layers: a ramp
    # between kept and divided frequencies, from the four numbers below;
    # rope_attention_factor 0 = 0.1 ln(rope_scaling) + 1) — models/llama.py
    # rope_frequencies
    rope_scaling_type: str = "linear"
    rope_original_max_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 0.0
    rms_norm_eps: float = 1e-5
    # Looped decoder (model name "llama"; models/llama.py's docstring): the
    # stack of num_layers blocks applied loop_steps times over the same
    # weights, each block with a norm after its two sublayers as well as
    # before (sandwich_norm: four scales a layer), a head and a scalar exit
    # gate after every pass; the loss (loss="looped_lm_xent") is the
    # expectation of the exits' cross-entropies under the gates' exit
    # distribution, less loop_entropy_beta x its entropy.
    loop_steps: int = 1
    sandwich_norm: bool = False
    loop_entropy_beta: float = 0.05
    # T5 family (models/t5.py): decoder stack depth (0 → = num_layers) and
    # the bucketed relative-position-bias geometry.
    decoder_layers: int = 0
    rel_pos_buckets: int = 32
    rel_pos_max_distance: int = 128
    # Tie the LM head to the shared embedding (t5: published v1.0
    # checkpoints tie + rescale decoder output by d_model**-0.5; set true
    # to load them via interop).
    tie_word_embeddings: bool = False
    # Memory: rematerialise each transformer block's activations in backward
    remat: bool = False
    # What remat may keep resident (models/remat.py — the selective
    # activation-checkpointing dial): "full" recomputes the block,
    # "dots" keeps matmul outputs (XLA dots_saveable), "dots_no_batch"
    # keeps only non-batch-dim matmuls. Every policy also keeps what a
    # Pallas attention kernel handed back (its output and log-sum-exp:
    # one (B, S, H x Dv) activation and a (B, H, S) fp32 row a layer),
    # so the backward pass does not launch the kernel's forward again.
    remat_policy: str = "full"
    # Fused chunked LM-head loss (llama/gpt2): head matmul + CE computed per
    # sequence chunk under remat so (B,S,V) logits never materialize
    # (losses.chunked_causal_ce). Requires loss="fused_causal_lm_xent".
    fused_lm_loss: bool = False
    # Attention backend for this process: auto (pallas on TPU when
    # supported+profitable, else XLA), or force xla / pallas / chunked
    # (pure-XLA flash-style query-chunked path — O(S*chunk) memory,
    # compiles on backends that can't take Mosaic kernels). The
    # PDTT_ATTENTION_IMPL env var overrides (ops/attention.py).
    attention_impl: str = "auto"
    # Sliding-window attention span in tokens (Mistral recipe): each query
    # attends to its trailing `attention_window` keys. 0 = full causal.
    # Llama family; composes with every backend: xla/chunked mask or
    # band-slice, pallas masks within tiles and skips out-of-band blocks,
    # ring attention skips out-of-band hops, ulysses windows its full-seq
    # local core.
    attention_window: int = 0
    # KV-cache STORAGE dtype for decode/serving ("" = compute dtype).
    # "float8_e4m3fn" halves cache HBM and the per-step cache read —
    # decode's bandwidth bill (the fp8-KV recipe of production servers);
    # llama + gpt2 families. Training attention is untouched.
    kv_cache_dtype: str = ""
    # Packed-block document isolation (llama/gpt2 training): >= 0 names
    # the EOS id that delimits documents inside packed seq_len blocks
    # (data/text.py packing). Attention is then masked across documents
    # and rope/wpe positions restart at 0 per document — each doc trains
    # exactly as if unpacked. -1 = off (simple packing: docs see their
    # pack-mates' tails; the GPT-2/llama-pretrain default).
    segment_eos_id: int = -1
    # Pipeline parallelism (model name "llama_pp"; SURVEY §2.3 PP row):
    # microbatch count (0 → = stage count), schedule ("gpipe" | "1f1b" |
    # "interleaved"), and chunks per device for the interleaved schedule.
    pipeline_microbatches: int = 0
    pipeline_schedule: str = "gpipe"
    pipeline_chunks: int = 2
    # Mixture-of-Experts (SURVEY §2.3 EP row; ops/moe.py). num_experts>1
    # swaps the dense MLP for top-k routed experts on every moe_every-th
    # block; expert params shard over the 'expert' mesh axis.
    num_experts: int = 0
    expert_top_k: int = 2
    expert_capacity_factor: float = 1.25
    moe_every: int = 1
    moe_aux_weight: float = 0.01
    # Router style: "topk" (GShard/Switch — tokens choose) or
    # "expert_choice" (experts choose their top-capacity tokens: perfect
    # load balance structurally, no balance loss; a token may be served
    # by 0..E experts). Caveat for causal LMs: expert-choice selection
    # ranks over the whole batch, so training is mildly non-causal
    # (ops/moe.py::expert_choice_dispatch docstring).
    moe_router: str = "topk"
    # expert_choice ranks tokens over the whole flattened batch, so a
    # causal-LM loss trained with it leaks future positions into routing.
    # The trainer refuses that combination unless this is set — an explicit
    # "I understand the Zhou et al. caveat" opt-in.
    moe_router_allow_noncausal: bool = False
    moe_zloss_weight: float = 1e-3
    # Hybrid decoder (model name "hybrid_lm"; models/hybrid.py): layers in
    # groups of layer_group_size, the last of each group latent attention
    # (MLA: kv_lora_rank, rope_head_dim rotated dims beside head_dim plain
    # ones), the others Kimi Delta Attention (ops/kda.py: a causal
    # depthwise conv of conv_kernel_size, a log-decay bounded below by
    # kda_gate_lower_bound). head_dim 0 means
    # hidden_size / num_heads. The first first_dense_layers layers carry a
    # dense FFN of mlp_dim; the others ONE CHIP'S SHARE of a routed layer
    # (ops/moe.py HeldExpertsMLP): the router spans num_experts in
    # moe_groups groups of which a token uses moe_topk_groups, picks
    # expert_top_k, scales by moe_routed_scale; experts_held experts from
    # id experts_held_first on, of width moe_mlp_dim, live here (0 = all)
    # beside one shared expert. There expert_capacity_factor bounds the
    # grouped product's rows: that many times the pairs uniform routing
    # would send to the held experts; a step past it keeps its old state
    # and reports update_skipped. moe_score is the router's rule:
    # "sigmoid" (with the bias that only selection sees) or "softmax"
    # (over all the experts, no bias leaf).
    # layer_kinds names each layer's mixer where "the last of each group"
    # does not say it: kda | mla | gqa_full | gqa_window | conv, one a
    # layer (empty: derived from layer_group_size). conv is a gated short
    # convolution (LFM2's: three products and an elementwise chain over
    # conv_kernel_size taps a channel, no S x S term). The two gqa kinds are
    # grouped-query softmax attention over num_kv_heads KV heads with a
    # per-head sigmoid gate, layer_heads[i] query heads on layer i (empty:
    # num_heads everywhere): gqa_full is causal, rotated by rope_theta
    # under rope_scaling / rope_scaling_type over the first
    # partial_rotary_factor of each head; gqa_window sees the trailing
    # attention_window keys and is rotated by window_rope_theta, plain,
    # over the whole head; partial_rotary_factor 0 is NO rotation (NoPE).
    # A family's mixers differ in plain fields beyond their kinds
    # (models/hybrid.py MixerVariants): kda_gate "bounded" (the log-decay
    # kda_gate_lower_bound * sigmoid(.)) or "softplus" (-exp(A_log)
    # softplus(.), unbounded below: ops/kda.py's second form);
    # kda_beta_scale 1 or 2 (step sizes up to 2: a transition with a
    # negative eigenvalue); kda_gate_rank r > 0 puts the decay gate and a
    # channel-wise output gate through r features (two products);
    # kda_out_gate / gqa_out_gate "head" (one scalar a head) or "channel";
    # gqa_out_gate "none": no gate at all. gqa_qk_norm: an RMSNorm over
    # each head's q and k before the rotation (two learned head_dim-vectors
    # a layer, shared by the heads).
    # heads_held query/KDA heads from heads_held_first on live here (0 =
    # all), with the KV heads the grouping gives them: one chip's share of
    # a tensor-parallel group's mixers, as experts_held is of the experts;
    # the held heads' projections, gates and rows of o_proj, a partial sum.
    # The latent kind has two families' forms: mla_qk_norm (RMSNorm over a
    # head's whole q and k), mla_out_gate "head" | "none", mla_rope
    # "halves" (dims i and i + rope/2 rotate together) | "pairs" (2i and
    # 2i+1); the defaults are the first preset's, False / "none" / "pairs"
    # DeepSeek-V3's plain form. moe_shared_mlp_dim is the shared expert's
    # own width (a family's n shared experts of width w are ONE SwiGLU of
    # n x w; 0: the routed experts' moe_mlp_dim; -1: the family has NO
    # shared expert, no such subtree). tie_word_embeddings reads the head
    # from the input table (no lm_head leaf). moe_bias_rate > 0 moves
    # a sigmoid router's selection bias after every optimizer step by the
    # auxiliary-loss-free balancing rule, b_e += rate x sign(mean(c) - c_e)
    # over the step's tokens c_e on each router output (ops/moe.py
    # balance_routers; steps.py): outside the gradient, the clip, AdamW's
    # moments and the decay, kept on a skipped step; 0 leaves the bias as
    # drawn. A hyper-parameter of the model's recipe, like the learning rate.
    head_dim: int = 0
    layer_kinds: tuple[str, ...] = ()
    layer_heads: tuple[int, ...] = ()
    partial_rotary_factor: float = 1.0
    window_rope_theta: float = 10000.0
    moe_score: str = "sigmoid"
    layer_group_size: int = 0
    first_dense_layers: int = 0
    kv_lora_rank: int = 512
    rope_head_dim: int = 64
    conv_kernel_size: int = 4
    kda_gate_lower_bound: float = -5.0
    moe_mlp_dim: int = 0
    moe_groups: int = 1
    moe_topk_groups: int = 1
    moe_routed_scale: float = 1.0
    experts_held: int = 0
    experts_held_first: int = 0
    heads_held: int = 0
    heads_held_first: int = 0
    kda_gate: str = "bounded"
    kda_beta_scale: float = 1.0
    kda_gate_rank: int = 0
    kda_out_gate: str = "head"
    gqa_out_gate: str = "head"
    gqa_qk_norm: bool = False
    mla_qk_norm: bool = True
    mla_out_gate: str = "head"
    mla_rope: str = "halves"
    moe_shared_mlp_dim: int = 0
    moe_bias_rate: float = 0.0
    # Fused elementwise block epilogues (ops/fused_update.py; vit/bert):
    # the bias+GELU MLP epilogue and (post-LN bert) the residual-add+
    # LayerNorm epilogue compute as single tagged expressions XLA keeps
    # in one elementwise kernel, and the tag ("fused_epilogue",
    # jax.ad_checkpoint.checkpoint_name) gives remat a handle — policy
    # "no_fused_epilogue" (models/remat.py) recomputes exactly these
    # cheap chains in backward instead of saving them. Param tree and
    # numerics are unchanged (same names, same math, same fp32 norms);
    # the knob exists so the A/B is one config flip.
    fused_epilogues: bool = False
    # AQT-style int8 quantized TRAINING ("" | "int8"; llama/llama_pp/gpt2):
    # attention + MLP matmuls run int8×int8→int32 on the MXU (2× bf16
    # MACs/cycle on v5e) with dynamic symmetric absmax scales and a
    # straight-through backward — quant.int8_dot_general. lm_head and MoE
    # experts stay in the compute dtype. Decode-side weight-only int8 is
    # separate (generate/bench --quantize int8).
    quant_training: str = ""


@dataclass
class DataConfig:
    """Input pipeline. ``batch_size`` is GLOBAL (summed over all hosts/chips),
    matching the reference's per-step effective batch under DDP."""

    # synthetic_images | cifar10 | imagenet_folder | synthetic_lm |
    # text_lm (real corpus) | text_mlm (real corpus when text_files set,
    # else synthetic masking stream)
    dataset: str = "synthetic_images"
    data_dir: str = ""
    # Host loader backend (SURVEY C17): "threads" (in-process pool) or
    # "grain" (Grain worker PROCESSES — the torch-DataLoader-worker model)
    loader: str = "threads"
    batch_size: int = 128
    eval_batch_size: int = 0  # 0 → = batch_size
    num_workers: int = 4
    prefetch: int = 2  # device-side double/triple buffer depth
    shuffle: bool = True
    drop_last: bool = True  # SPMD needs static shapes; pad-or-drop final batch
    seed: int = 0
    # "" | "inverse_class" — torch WeightedRandomSampler recipe: train-time
    # draws WITH replacement ∝ 1/class-frequency (array datasets w/ labels)
    weighted_sampling: str = ""
    # Elastic resharding (docs/elastic.md): shard the input stream by the
    # LAUNCHER world (NUM_PROCESSES / PROCESS_ID — elastic.elastic_world)
    # instead of the jax process world. For tpurun gangs whose workers
    # are single-process jax runtimes (the CPU drills; one-runtime-per-
    # host deployments): a degraded generation then recomputes per-host
    # shards from the SHRUNKEN world mid-epoch — the global batch stays
    # fixed, per-host batch rescales, and the union of all hosts' batch
    # b is the same global index set at any world size.
    elastic_shards: bool = False
    # Batch augmentation (device-side, ops/mixup.py — the torchvision/timm
    # --mixup-alpha/--cutmix-alpha recipe knobs); 0.0 disables.
    mixup_alpha: float = 0.0
    cutmix_alpha: float = 0.0
    mixup_switch_prob: float = 0.5
    # Native libjpeg batch decode for imagenet_tar (native/jpegdec.cpp):
    # decode + crop-resize + normalize in C++ threads instead of per-item
    # PIL. Falls back silently when the lib can't build, shards hold PNGs,
    # or RandAugment is on (PIL-op chain). Same crop policy, plain-bilinear
    # resampling (PIL filters on downscale — statistically equivalent).
    native_decode: bool = False
    # Shared-memory multi-process decode plane (data/workers.py): >0
    # runs decode/augment in N forked worker processes writing decoded
    # batches into preallocated shared-memory ring slots (no pixel
    # pickling), fronting BOTH loaders. 0 = in-process (threads for the
    # "threads" loader, grain's own machinery for "grain"). Clamped to
    # cpu_count-1 (workers.pool_budget); batch composition and resume
    # semantics are byte-identical to the in-process path.
    mp_workers: int = 0
    # Ring depth for the shared-memory pool (0 -> mp_workers + 2).
    mp_slots: int = 0
    # Packed pre-decoded sample cache (data/packed_cache.py): directory
    # of fixed-record u8 shards built by tools/pack_dataset.py. When set
    # on an image dataset, a valid cache for the split replaces the
    # decode path with one mmap'd strided read (hit/miss counted in the
    # registry); absent/invalid caches fall through to the original
    # dataset. Dataset name "packed_images" reads shards directly from
    # data_dir (dir or glob).
    packed_cache_dir: str = ""
    # Verify shard CRCs at open (full payload read; tools and tests —
    # training opens skip it and rely on the pack-time CRC).
    packed_verify: bool = False
    # Device-side augmentation (ops/device_augment.py): datasets that
    # can ship raw uint8 pixels skip host-side crop/flip/RandAugment/
    # normalize; the jitted train step applies them on-device under the
    # same PRNG-folding discipline as dropout. Host path is unchanged
    # when off; datasets that cannot ship u8 (synthetic/LM/native-decode
    # tar) ignore the flag.
    device_augment: bool = False
    # Host-side RandAugment (data/augment.py; ImageFolder train path).
    # num_ops 0 disables; magnitude in [0, 30] (torchvision's 31 bins).
    # With device_augment on, the RandAugment op space moves on-device
    # (photometric/affine u8 ops — ops/device_augment.py documents the
    # semantic deltas vs the PIL chain).
    randaugment_num_ops: int = 0
    randaugment_magnitude: int = 9
    # LM datasets
    seq_len: int = 512
    # Decoder-side target length for seq2seq datasets (0 → = seq_len).
    tgt_seq_len: int = 0
    mlm_prob: float = 0.15
    # Real-text corpus (datasets text_lm / text_mlm, data/text.py): glob of
    # local .txt/.jsonl files, and an optional local HF-tokenizer directory
    # (absent → built-in byte-level tokenizer, vocab 259).
    text_files: str = ""
    tokenizer_path: str = ""
    # text_files matching one .bin selects the memory-mapped pre-tokenized
    # stream (nanoGPT-style flat token file); this is its element dtype.
    token_bin_dtype: str = "uint16"
    # Synthetic dataset length (steps worth of fake data per epoch)
    synthetic_size: int = 51200


@dataclass
class OptimConfig:
    """Optimizer + LR schedule (reference: torch.optim.SGD / LAMB — SURVEY C20)."""

    # sgd | momentum | adamw | lamb | adam | lars | adafactor | muon
    name: str = "sgd"
    learning_rate: float = 0.1
    warmup_steps: int = 0
    # constant | cosine | step | linear | polynomial | onecycle |
    # cosine_restarts
    schedule: str = "cosine"
    poly_power: float = 1.0  # polynomial schedule exponent (1.0 = linear)
    # onecycle: fraction of the horizon spent ramping up (torch OneCycleLR
    # pct_start); cosine_restarts: first cycle length in optimizer updates
    # (0 → horizon/4) and per-restart length multiplier (torch T_0/T_mult).
    onecycle_pct_start: float = 0.3
    restart_period: int = 0
    restart_mult: float = 1.0
    # step schedule
    step_decay_rate: float = 0.1
    step_decay_every: int = 30  # epochs
    momentum: float = 0.9
    nesterov: bool = False
    weight_decay: float = 1e-4
    # No-decay param groups (the torch-recipe `no_decay=['bias','LayerNorm']`
    # pattern): comma-separated regexes matched against the '/'-joined param
    # path; matching params skip weight decay (and LARS trust-ratio scaling).
    # Flax naming: biases are 'bias', Layer/RMS/BatchNorm scales are 'scale'.
    decay_exclude: str = ""
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    grad_clip_norm: float = 0.0  # 0 → off
    # ReduceLROnPlateau analogue (optax.contrib.reduce_on_plateau), driven
    # by the per-step training loss inside the jitted step (torch drives
    # it with whatever metric you pass — commonly val loss per epoch; here
    # the signal is the train loss, smoothed over plateau_accumulation
    # updates). plateau_factor > 0 enables; patience/cooldown count
    # optimizer updates.
    plateau_factor: float = 0.0
    plateau_patience: int = 10
    plateau_cooldown: int = 0
    plateau_accumulation: int = 1
    plateau_min_scale: float = 0.0
    # Keep optimizer state (adam/lamb moments, momentum) in pinned HOST
    # memory between steps — the ZeRO-Offload analogue, via JAX memory
    # kinds. Frees ~2 params-worth of HBM for adam-family optimizers at the
    # cost of host<->HBM transfers XLA overlaps with compute. TPU-only
    # (the CPU test backend cannot execute the placement custom-call).
    offload_state: bool = False
    # Storage dtype for optimizer moment/momentum accumulators ("" → fp32).
    # "bfloat16" halves first-moment HBM for adam/adamw/lamb (and the SGD
    # momentum buffer) — the update math stays fp32, only storage narrows.
    # Second moments (nu) always stay fp32: bf16's 8-bit mantissa loses the
    # small squared-gradient increments that drive the Adam denominator.
    moment_dtype: str = ""
    # adafactor: factor second moments above this dim (optax default 128);
    # momentum is a SEPARATE knob (0 → stateless, the paper default) so the
    # SGD-oriented `momentum=0.9` default can't silently re-add the
    # first-moment buffer adafactor exists to avoid.
    adafactor_min_dim_factored: int = 128
    adafactor_momentum: float = 0.0
    # muon: momentum coefficient for the orthogonalized branch (matrix
    # params); beta1/beta2 configure its adam branch (everything else).
    muon_beta: float = 0.95
    # Layer-wise LR decay (timm/BEiT fine-tune recipe): depth-d params'
    # updates scale by decay^(max_depth - d); 1.0 → off. Head/final norm
    # keep full LR, embeddings/stem train slowest.
    layer_lr_decay: float = 1.0
    accum_steps: int = 1  # optax.MultiSteps microbatching (≡ DDP no_sync)
    # Polyak/EMA weight averaging (torch-recipe "model EMA"): decay per
    # step, 0 → off. Eval runs on the EMA mirror when enabled.
    ema_decay: float = 0.0
    # Stochastic Weight Averaging (torch.optim.swa_utils): from the
    # swa_start_step-th OPTIMIZER UPDATE on (denominated like
    # warmup_steps — under accum_steps one update spans accum micro-
    # steps), the mirror keeps the EQUAL-WEIGHT running mean of params
    # sampled every swa_every updates; eval runs on it (same mirror as
    # EMA — the two are mutually exclusive). Like torch's AveragedModel,
    # BN stats are NOT re-estimated automatically (torch needs an
    # explicit update_bn pass too).
    swa_start_step: int = 0  # 0 → off
    swa_every: int = 1
    # SWALR: constant LR once SWA collection starts (0 → keep the base
    # schedule running)
    swa_lr: float = 0.0
    # torch swa_utils.update_bn analogue: after training, re-estimate BN
    # statistics for the AVERAGED weights over this many training
    # batches (averaged weights + stale stats is the classic SWA
    # mistake). 0 → off; no-op for BN-free models. Runs before the
    # final evaluation when SWA/EMA is on.
    swa_update_bn_batches: int = 0
    # Grad-compression hook (SURVEY C8 ddp_comm_hooks equivalent):
    # "none" | "bf16" | "fp16" | "powersgd" (grad_hooks.py)
    grad_hook: str = "none"
    powersgd_rank: int = 2
    # Final LR fraction for cosine
    end_lr_factor: float = 0.0


@dataclass
class PrecisionConfig:
    """Mixed precision policy. Replaces autocast + GradScaler (SURVEY C18/C19):
    params stay fp32, compute runs in ``compute_dtype``. bf16 needs no loss
    scaling on TPU; ``loss_scale`` keeps the reference's GradScaler knob for
    fp16 experiments (default off)."""

    compute_dtype: str = "float32"  # float32 | bfloat16
    param_dtype: str = "float32"
    # "none" | "dynamic" | a float for static scaling
    loss_scale: str = "none"
    loss_scale_init: float = 2.0**15
    loss_scale_growth_interval: int = 2000


@dataclass
class MeshConfig:
    """Device mesh axis sizes. -1 on one axis → fill with remaining devices.

    stage   — pipeline parallelism (GPipe/1F1B microbatch schedules)
    data    — batch sharding (DP; reference DDP, SURVEY §2.3)
    fsdp    — parameter sharding (ZeRO/FSDP → GSPMD, BASELINE.json:11)
    expert  — MoE expert parallelism (token all-to-all dispatch)
    tensor  — megatron TP on heads / mlp hidden
    context — sequence/ring-attention parallelism (SURVEY §5.7)
    """

    stage: int = 1
    data: int = -1
    fsdp: int = 1
    expert: int = 1
    tensor: int = 1
    context: int = 1
    # Which mesh axes batch is sharded over (data+fsdp is the common combo).
    batch_axes: tuple[str, ...] = ("data", "fsdp")
    # ZeRO stage on the 'fsdp' axis (torch FSDP ShardingStrategy analogue,
    # steps.state_shardings): 3 = params+optimizer sharded (FULL_SHARD,
    # default); 1 = optimizer-state-only sharding, params replicated
    # (fits when weights fit per-chip but adam moments don't).
    zero_stage: int = 3
    # Attention algorithm when context > 1 (SURVEY §5.7):
    #   ring    — lax.ppermute KV rotation around the ICI ring; any size
    #   ulysses — all-to-all head↔seq swap; needs heads % context == 0
    context_impl: str = "ring"
    # Ring sequence layout: "zigzag" gives each device chunks (i, 2n−1−i)
    # so causal-triangle work balances across the ring
    # (ops/ring_attention.py::zigzag_perm). Exact at any size; costs one
    # gather each way per attention call. The ~2× causal saving is
    # realized by the pallas chunk backend's block skipping, which needs
    # the half-chunk to cover ≥1 KV chunk of the kernel's tile rule:
    # S_local/2 ≥ block_k (i.e. seq/ring ≥ 1024 at the 512-wide chunks
    # ops/flash_attention.tile_sizes gives such lengths) — the
    # long-context regime CP exists for. Below that (or on the einsum
    # backend) zigzag is correct but pays the gathers for no win.
    # Ignored by ulysses / non-causal attention.
    context_layout: str = "contiguous"
    # Megatron-style sequence parallelism (SURVEY §2.3 SP row): with
    # tensor>1, shard activations along sequence over the 'tensor' axis
    # between TP matmuls (norms/residuals run seq-sharded; GSPMD inserts
    # the all-gather/reduce-scatter pair at the matmul boundaries).
    sequence_parallel: bool = False


# XLA flag preset for the overlapped-collectives path (steps.py
# re-exports; bench.py/train.py apply it to XLA_FLAGS before the first
# jax import): the latency-hiding scheduler + async collective fusion
# are what let the per-bucket in-scan reductions actually overlap the
# next microbatch's compute instead of serializing after it. Defined
# here (jax-free module) so host-side entrypoints can set the env
# without importing a backend.
LATENCY_HIDING_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true "
    "--xla_tpu_overlap_compute_collective_tc=true"
)


def ensure_latency_hiding_flags(env=None) -> bool:
    """Append the scheduler preset to XLA_FLAGS unless already present.
    Returns True when the env was modified. Only effective if called
    before the first jax backend initialization — which is why it lives
    HERE (jax-free module) and not in steps.py: entrypoints import this
    before any backend-registering import. TPU backends only — XLA:CPU
    rejects unknown ``--xla_tpu_*`` flags fatally, so callers gate on
    the resolved platform (see bench.py)."""
    import os

    env = env if env is not None else os.environ
    flags = env.get("XLA_FLAGS", "")
    if "--xla_tpu_enable_latency_hiding_scheduler" in flags:
        return False
    env["XLA_FLAGS"] = (flags + " " + LATENCY_HIDING_XLA_FLAGS).strip()
    return True


@dataclass
class TrainStepConfig:
    """Compute-graph optimization layer for the train step (steps.py +
    ops/fused_update.py; docs/performance.md "Compute side"). All knobs
    default off — the single-shot GSPMD step is the reference program
    and every knob here is measured against it."""

    # Microbatched train step: lax.scan over N microbatches inside ONE
    # donated step executable, grads accumulated in the carry and the
    # (clip → update → gate) epilogue applied once on the accumulated
    # mean — the activation-memory/overlap twin of optim.accum_steps
    # (optax.MultiSteps), which instead runs N separate host-driven
    # micro-steps. 1 = off. Mutually exclusive with optim.accum_steps>1
    # (both would compound). The global batch must divide by it; LR
    # schedules count optimizer steps as before (one scan = one step).
    grad_accum_steps: int = 1
    # Overlapped gradient collectives (the DDP-reducer analogue, SURVEY
    # C7/[TORCH] reducer.hpp:285): run the step under shard_map over the
    # batch axes and issue per-BUCKET grad reductions inside the
    # accumulation scan — microbatch i's collectives overlap microbatch
    # i+1's compute under XLA's latency-hiding scheduler
    # (steps.LATENCY_HIDING_XLA_FLAGS). Requires params/opt state
    # replicated over the batch axes (pure DP or mesh.zero_stage=1
    # layouts); refused loudly otherwise.
    overlap_collectives: bool = False
    # Bucket size for the per-bucket reductions, mirroring DDP's
    # bucket_cap_mb=25 default; buckets fill in REVERSE parameter order
    # (the order backward produces grads — reducer semantics).
    grad_bucket_mb: int = 25
    # Fused optimizer epilogue (ops/fused_update.py): clip-by-global-
    # norm + optimizer update + non-finite gate computed in ONE pass
    # over the grad tree (per-leaf select against the old state) instead
    # of the chain's three passes plus the gate's whole-tree two-branch
    # select. Numerically identical to the optax chain — which remains
    # the reference oracle (tests pin fused == chain bit-for-bit,
    # LR-cooldown leaf included); configs the fast path cannot express
    # (plateau, layer_lr_decay, grad hooks, exotic optimizers) are
    # refused loudly rather than silently falling back.
    fused_epilogue: bool = False


@dataclass
class CheckpointConfig:
    """Orbax-backed checkpointing (SURVEY §5.4). ``resume='auto'`` restores the
    latest step if present — the default path, not a flag (SURVEY §5.3b)."""

    dir: str = "checkpoints"
    save_every_steps: int = 1000
    max_to_keep: int = 3
    resume: str = "auto"  # auto | none | <explicit path>
    async_save: bool = True
    # Track the best eval checkpoint (the torch-recipe `model_best.pth`
    # pattern): "" → off; else an eval-metric name ("accuracy", "loss", …).
    # When the metric improves, the state is saved under <dir>/best
    # (max_to_keep=1); resume still uses the latest cadence checkpoint.
    best_metric: str = ""
    best_mode: str = "max"  # max | min
    # Per-step integrity manifests (faults/integrity.py): after each
    # Orbax commit, inventory the step's files (sizes + content hashes)
    # under <dir>/manifests/; restore verifies and falls back past
    # corrupt/partial steps to the newest verified one.
    integrity: bool = True
    # ---- tiered async checkpointing plane (ckpt/; docs/checkpointing.md)
    # tiered=true replaces the plain Orbax manager with the tiered one:
    # at a save boundary the step loop blocks only for the device->host
    # snapshot copy (ckpt_blocking_ms); a background persister thread
    # runs seal -> local-disk spill -> peer publish -> Orbax write +
    # manifest (ckpt_persist_ms), with at most ONE persist in flight
    # (an early next boundary waits — the ckpt.drain goodput bucket).
    # Restores (sentinel rewind, elastic resume) try RAM -> local disk
    # -> peer store -> Orbax, each tier verified.
    tiered: bool = False
    # Hot retention: keep the newest hot_keep sealed snapshots per tier
    # (RAM and local disk age under the same policy), plus every step
    # divisible by keep_every (0 = off). The newest manifest-verified
    # persistent step and the newest sealed hot step are always pinned.
    hot_keep: int = 2
    keep_every: int = 0
    # Local-disk spill tier: per-host sealed-snapshot copies that
    # survive a process kill (same-host elastic restart restores in ms).
    # Root dir "" -> <dir>/hot (each host appends host_<n>) — a
    # single-host convenience. On a multi-host deployment whose <dir>
    # is shared/network storage, point hot_dir at NODE-LOCAL scratch
    # (/tmp, local SSD): spilling to the same shared FS Orbax writes
    # would double persistent-storage traffic and forfeit the
    # fast-local-restart property the tier exists for.
    hot_disk: bool = True
    hot_dir: str = ""
    # Cross-host peer exchange over the launcher's KV store: each host
    # publishes its newest sealed snapshot (<= peer_publish_max_bytes;
    # larger models skip publication and keep disk+Orbax tiers) and a
    # restoring worker fetches it before touching persistent storage.
    peer_fetch: bool = True
    peer_publish_max_bytes: int = 64 * 1024 * 1024


@dataclass
class ObsConfig:
    """Observability: metrics cadence, profiler window, failure detection
    (SURVEY §5.1-5.5)."""

    log_every_steps: int = 50
    jsonl_path: str = ""  # "" → <ckpt dir>/metrics.jsonl
    tensorboard: bool = False
    # Legacy fixed profiler window — now a shim over the managed
    # profiler plane (obs/profiler.py): profile_num_steps > 0 pre-queues
    # ONE capture at profile_start_step writing into profile_dir's root
    # (old output layout, exempt from the capture ring).
    profile_start_step: int = 0  # 0 → profiling off
    profile_num_steps: int = 0
    profile_dir: str = "profiles"
    # ---- event journal (obs/events.py; docs/observability.md schema).
    # Append-only per-host JSONL of structured run events (faults,
    # sentinel verdicts, ckpt traffic, restarts, captures) merged by
    # tools/timeline_report.py. "" dir → <checkpoint.dir>/events; the
    # PDTT_EVENTS_DIR env var (tpurun --events-dir) overrides "".
    events: bool = True
    events_dir: str = ""
    # ---- distributed request tracing (obs/tracing.py): trace spill
    # directory for the tail-based sampler ("" → <checkpoint.dir>/traces,
    # beside the event journal; PDTT_TRACE_DIR overrides ""), the random
    # baseline retention percentage, and the slow-trace retention
    # threshold. Trainer spans carry (gen, step) correlation tags so a
    # serving tail on a co-resident host lines up against training.
    trace_dir: str = ""
    trace_sample_pct: float = 0.0
    trace_keep_slow_ms: float = 250.0
    # ---- managed profiler plane (obs/profiler.py): bounded N-step
    # jax.profiler windows with an artifact ring, triggered on cadence,
    # on demand (trigger file / POST /profile; store-coordinated under
    # tpurun so all hosts capture the same steps), and by anomaly hooks.
    profile_window_steps: int = 5   # steps per managed capture
    profile_every_steps: int = 0    # cadence trigger (0 = off)
    profile_ring: int = 4           # completed capture dirs retained
    profile_trigger_file: str = ""  # "" → <checkpoint.dir>/PROFILE
    # Anomaly auto-capture (sentinel loss-spike, straggler blame, the
    # step-time/input-stall regression detectors). Off by default: an
    # unattended jax.profiler session is a real side effect (CPU+disk)
    # the operator opts into; anomaly EVENTS are journaled regardless.
    profile_on_anomaly: bool = False
    profile_cooldown_steps: int = 200  # min steps between auto-captures
    # Rolling median+MAD regression detectors (sentinel/numeric.py
    # SpikeDetector pointed at wall-clock health): step time per step,
    # input-stall % per log window.
    profile_regress_window: int = 64
    profile_regress_sigma: float = 8.0
    profile_regress_min_samples: int = 16
    profile_regress_min_rel: float = 0.5
    profile_stall_min_pct: float = 5.0  # abs floor for stall anomalies
    # Straggler blame trigger: cluster max step-time p50 >= ratio x the
    # median (needs obs.straggler_metrics + multi-host). 0 = off.
    profile_straggler_ratio: float = 2.0
    profile_top_ops: int = 5        # rows in the journaled xplane summary
    # ---- perf ledger (obs/perf.py; docs/performance.md): rank 0
    # appends one throughput/MFU/stall-split row per fit() to an
    # append-only JSONL the regression gate (tools/perf_ledger --check)
    # compares across runs. "" path → <checkpoint.dir>/perf_ledger.jsonl
    # (the PDTT_PERF_LEDGER env var overrides "").
    perf_ledger: bool = True
    perf_ledger_path: str = ""
    heartbeat_timeout_s: float = 0.0  # 0 → heartbeat monitor off
    debug_nans: bool = False
    # Cross-host input-divergence check cadence (0 → off); SURVEY §5.2
    check_input_sync_every: int = 0
    # Fault injection (SURVEY §5.3c): hard-kill this process when the step
    # counter reaches this value — but only in restart generation 0, so a
    # tpurun-supervised job crashes exactly once and must recover through
    # checkpoint resume. 0 → off. Test hook; no effect on saved state.
    # DEPRECATED: kept as a back-compat shim routed through the fault
    # registry as ``step.crash@step=N`` — new scenarios should use
    # ``faults.inject`` (docs/fault_tolerance.md), which composes
    # multiple faults per run.
    fault_inject_at_step: int = 0
    # Stall injection (SURVEY §5.3a): WEDGE this process (sleep forever,
    # heartbeat never beats) when the step counter reaches this value —
    # generation 0 only, like fault_inject_at_step. Exercises the full
    # stalled-step chain: heartbeat fires → flight-recorder dump → abort
    # (exit 134) → gang restart → checkpoint resume. 0 → off. Test hook.
    stall_inject_at_step: int = 0
    # Log device memory (HBM bytes_in_use / peak) with train metrics.
    # No-op on backends that don't report memory_stats (CPU).
    log_memory: bool = False
    # Live Prometheus exposition sidecar (obs/exposition.py): 0 = off
    # (default — a port bind is a side effect), >0 = bind that port,
    # -1 = ephemeral OS-assigned port (tests / several trainers per
    # host; read it back from Trainer.metrics_server.port). Serves
    # GET /metrics (text format v0.0.4) and /healthz. A fixed port
    # already bound by another local worker falls back to an ephemeral
    # one (logged once); under tpurun the ACTUAL bound port is
    # published to the launcher store as an obs endpoint record, so
    # the fleet collector (obs/collector.py) scrapes the right port
    # either way.
    metrics_port: int = 0
    # Chrome trace.json of host spans (obs/spans.py), written by process
    # 0 when fit() ends ("" → <checkpoint.dir>/trace.json). Load in
    # chrome://tracing or Perfetto next to the xplane device trace.
    trace_path: str = ""
    # Cross-host straggler aggregation (obs/cluster.py): at log cadence
    # every host contributes {step_time_p50, input_stall_pct, hbm_used}
    # via process_allgather; rank-0 logs cluster min/med/max plus the
    # arg-max host id. Only adds log keys when process_count > 1; the
    # collective runs off the step path (log cadence, consumer thread).
    straggler_metrics: bool = True
    # Per-top-level-module grad norms in the train metrics
    # (grad_norm/<module> keys) — which block explodes/vanishes.
    log_module_grad_norms: bool = False
    # Model-health observability plane (obs/model_health.py;
    # docs/observability.md "Model health"): the in-graph training-
    # dynamics pass (per-module grad/param/update norms + update-to-
    # param ratios, ops/model_health.py) in the step metrics, plus the
    # host-side monitor that journals divergence early-warnings under
    # the ``model`` event category and can arm the sentinel rewind /
    # profiler hooks BEFORE the loss diverges. Bitwise no-op on the
    # update path when off.
    model_health: bool = False
    # Persistent XLA compilation cache dir ("" → leave jax's default): cuts
    # the minutes-scale recompiles of big GSPMD programs across job restarts
    # (SURVEY §7.4.5) — the torch.compile cache analogue. NOTE: the jax
    # setting is process-global; "" does not reset a value set by an
    # earlier Trainer in the same process.
    compile_cache_dir: str = ""


@dataclass
class FaultsConfig:
    """Fault injection + recovery policies (faults/;
    docs/fault_tolerance.md has the point catalog, schedule grammar and
    recovery matrix)."""

    # Declarative injection schedule: each entry is
    # "<point>@key=val[:key=val...]", e.g.
    #   ("ckpt.save_io@step=3:count=2", "preempt.sigterm@step=5").
    # Keys: step (trainer step >= N), call (Nth traversal), p
    # (per-traversal probability, seeded by `seed`), count (times to
    # fire, default 1), gen (restart generation, default 0; -1 = all),
    # rc (step.crash exit code), delay (step.straggle seconds). The
    # PDTT_FAULTS env var appends more specs (subprocess workers,
    # serving tools).
    inject: tuple[str, ...] = ()
    # Seed for probabilistic (p=) specs — chaos soak reproducibility.
    seed: int = 0
    # SIGTERM → set-a-flag; the train loop forces a synchronized
    # checkpoint at the next step boundary, writes a `preempted` marker
    # in the summary record, and exits cleanly (preempt_exit_code) —
    # at most one step lost instead of save_every_steps. Off by
    # default: the legacy behavior (watchdog dumps diagnostics and
    # exits 143, fit()'s finally saves on the way down) remains.
    graceful_preemption: bool = False
    preempt_exit_code: int = 0
    # Retry policy for fault-guarded I/O (checkpoint save, record
    # decode): exponential backoff base*2^k capped at max, +jitter.
    retry_max_attempts: int = 3
    retry_base_delay_s: float = 0.05
    retry_max_delay_s: float = 2.0


@dataclass
class SentinelConfig:
    """Training health sentinel (sentinel/; docs/sentinel.md): numeric
    fault guard + auto-rewind + cross-host hang diagnosis — recovery for
    the faults that DON'T crash."""

    # Master switch for the numeric plane: in-graph update gate (a
    # non-finite grad/loss skips the optimizer update; params unchanged,
    # sentinel_skipped_steps_total{reason=nonfinite}), the rolling
    # loss-spike detector, and the auto-rewind loop. Off by default:
    # spike/streak tracking reads the loss to host every step, which
    # serializes async dispatch — a real (small) cost the operator opts
    # into.
    enabled: bool = False
    # Loss-spike detector (sentinel/numeric.py): a loss deviating from
    # the rolling-window median by more than spike_sigma robust sigmas
    # (MAD * 1.4826) — and by more than spike_min_rel of the median, the
    # floor that keeps a near-zero early MAD from flagging ordinary
    # jitter — counts as a bad step. Only healthy losses enter the
    # window, so divergence can't drag the baseline up after itself.
    spike_window: int = 64
    spike_sigma: float = 6.0
    spike_min_samples: int = 8
    spike_min_rel: float = 0.1
    # Auto-rewind: after this many CONSECUTIVE bad steps (non-finite or
    # spiking), restore the newest integrity-verified checkpoint
    # (latest_good_step), fast-forward the data stream to it (the exact
    # mid-epoch start_batch resume), scale the LR by lr_cooldown_factor
    # (compounds per rewind; persists in the checkpointed opt state) and
    # continue. max_rewinds bounds a run that keeps diverging — past it
    # the sentinel raises instead of looping restore-diverge forever.
    max_consecutive_bad: int = 3
    lr_cooldown_factor: float = 0.5
    max_rewinds: int = 8
    # Liveness plane (sentinel/liveness.py): with a tpurun store present
    # and hang_timeout_s > 0, every host publishes {step, ts} heartbeats
    # at heartbeat_every_steps cadence and rank 0 monitors staleness on
    # its OWN clock (clock-skew immune). On a hang: blamed-host
    # diagnosis (id + open spans), cluster-wide flight-recorder dump,
    # exit with hang_exit_code so the elastic agent gang-restarts.
    # Size hang_timeout_s well above a step time and the longest
    # checkpoint save; hosts that never heartbeat (first compile) are
    # never blamed. 0 = off.
    hang_timeout_s: float = 0.0
    hang_poll_s: float = 1.0
    hang_exit_code: int = 43
    heartbeat_every_steps: int = 1


@dataclass
class LoraConfig:
    """Parameter-efficient fine-tuning (lora.py). ``rank=0`` disables.

    Freeze the base model, train rank-r adapters on the projections whose
    param path matches ``targets``; merge for export with lora.strip().
    Beyond-reference capability (the [SPEC] harness has no PEFT) built on
    the same config/checkpoint interfaces (SURVEY H7/H8).
    """

    rank: int = 0
    alpha: float = 16.0
    # Regex over '/'-joined param paths; adapters attach to matching 2-D
    # Dense / 3-D DenseGeneral `kernel` leaves. Default covers the
    # llama/gpt2/bert/vit attention projections (torch-PEFT's customary
    # default is q/v only; we take all four — adapters are cheap, quality
    # is not).
    targets: str = (
        r"(q_proj|k_proj|v_proj|o_proj|query|key|value|attn_out"
        r"|attn/c_proj)/kernel$")
    # 3-D DenseGeneral kernels matching this regex are OUTPUT projections
    # — contracted (input) dims first, (H, Dh, d_out) — so the rank-r
    # factors bridge (H*Dh) -> out instead of in -> (H, Dh). Extend when
    # targeting a new model family whose out-projection has another name.
    out_proj_targets: str = r"(o_proj|attn_out|out_proj|attn/c_proj)/kernel$"
    # Additional full-rank leaves to leave trainable (regex, "" = none),
    # e.g. r"(final_norm|/bias$)" for norm-and-bias tuning a la BitFit.
    extra_trainable: str = ""
    # Warm-start: restore base params (only) from this run directory's
    # latest checkpoint before training — the "load pretrained, add
    # adapters" workflow. "" = train from fresh init (tests/debug).
    base_checkpoint: str = ""


@dataclass
class DistillConfig:
    """Knowledge distillation (distill.py). Enabled when
    ``teacher_checkpoint`` names a checkpoint directory; the teacher's
    architecture is read from that checkpoint's saved config, so nothing
    about the teacher is re-declared here."""

    teacher_checkpoint: str = ""
    # total = alpha * hard_loss + (1 - alpha) * kd_term
    alpha: float = 0.5
    # Softmax temperature for both teacher and student in the KD term
    # (the kd gradient is scaled by T^2 per Hinton et al. 2015).
    temperature: float = 2.0


@dataclass
class TrainConfig:
    """Root config. Serialises to/from JSON; dotted-path CLI overrides."""

    preset: str = ""
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    precision: PrecisionConfig = field(default_factory=PrecisionConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    faults: FaultsConfig = field(default_factory=FaultsConfig)
    sentinel: SentinelConfig = field(default_factory=SentinelConfig)
    lora: LoraConfig = field(default_factory=LoraConfig)
    distill: DistillConfig = field(default_factory=DistillConfig)
    # Compute-graph optimization layer (steps.py / ops/fused_update.py):
    # microbatched scan step, overlapped bucketed collectives, fused
    # optimizer epilogue. docs/performance.md "Compute side".
    train: TrainStepConfig = field(default_factory=TrainStepConfig)
    # Train loop horizon: epochs if >0, else total_steps.
    epochs: int = 0
    total_steps: int = 1000
    eval_every_steps: int = 0  # 0 → eval at epoch boundaries only
    seed: int = 42
    # Loss: "softmax_xent" (classification) | "mlm_xent" |
    # "causal_lm_xent" | "seq2seq_xent" | "fused_causal_lm_xent" |
    # "dpo" (preference pairs vs the frozen reference named by
    # distill.teacher_checkpoint; losses.make_dpo_loss)
    loss: str = "softmax_xent"
    # DPO temperature (the beta in -log sigmoid(beta * margin))
    dpo_beta: float = 0.1
    # torch CrossEntropyLoss(label_smoothing=) analogue (softmax_xent only)
    label_smoothing: float = 0.0

    # ------------------------------------------------------------------ io
    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "TrainConfig":
        kwargs: dict[str, Any] = {}
        for name in _fields(cls):
            if name not in d:
                continue
            v = d[name]
            if name in _SECTIONS:
                kwargs[name] = _SECTIONS[name](**_coerce_section(_SECTIONS[name], v))
            else:
                kwargs[name] = v
        return cls(**kwargs)

    @classmethod
    def from_json(cls, s: str) -> "TrainConfig":
        return cls.from_dict(json.loads(s))

    # ------------------------------------------------------- dotted access
    def override(self, dotted: str, value: str) -> None:
        """Apply one ``section.field=value`` override, coercing to the field type."""
        parts = dotted.split(".")
        obj: Any = self
        for p in parts[:-1]:
            if not hasattr(obj, p):
                raise KeyError(f"no config section {p!r} in {dotted!r}")
            obj = getattr(obj, p)
        leaf = parts[-1]
        if not hasattr(obj, leaf):
            raise KeyError(f"no config field {leaf!r} in {dotted!r}")
        cur = getattr(obj, leaf)
        setattr(obj, leaf, _coerce(value, cur))

    def apply_overrides(self, pairs: list[str]) -> None:
        for pair in pairs:
            if "=" not in pair:
                raise ValueError(f"override must be key=value, got {pair!r}")
            k, v = pair.split("=", 1)
            self.override(k.strip(), v.strip())


_SECTIONS = {
    "model": ModelConfig,
    "data": DataConfig,
    "optim": OptimConfig,
    "precision": PrecisionConfig,
    "mesh": MeshConfig,
    "checkpoint": CheckpointConfig,
    "obs": ObsConfig,
    "faults": FaultsConfig,
    "sentinel": SentinelConfig,
    "lora": LoraConfig,
    "distill": DistillConfig,
    "train": TrainStepConfig,
}


def _coerce_section(cls, d: dict[str, Any]) -> dict[str, Any]:
    names = _fields(cls)
    out = {}
    for k, v in d.items():
        if k in names:
            if isinstance(v, list):
                v = tuple(v)
            out[k] = v
    return out


def _coerce(value: str, current: Any) -> Any:
    """Coerce a CLI string to the type of the current value."""
    if isinstance(current, bool):
        if value.lower() in ("1", "true", "yes", "on"):
            return True
        if value.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"bad bool {value!r}")
    if isinstance(current, int):
        return int(value)
    if isinstance(current, float):
        return float(value)
    if isinstance(current, tuple):
        return tuple(x.strip() for x in value.split(",") if x.strip())
    return value


# ============================================================== presets
# The BASELINE.json:7-11 acceptance matrix.

def _resnet18_cifar10() -> TrainConfig:
    """BASELINE.json:7 — ResNet-18 on CIFAR-10, single-process smoke config."""
    c = TrainConfig(preset="resnet18_cifar10")
    c.model = ModelConfig(name="resnet18", num_classes=10, image_size=32)
    c.data = DataConfig(dataset="cifar10", batch_size=128)
    c.optim = OptimConfig(
        name="momentum", learning_rate=0.1, momentum=0.9, weight_decay=5e-4,
        schedule="cosine", warmup_steps=200,
    )
    c.epochs = 30
    c.loss = "softmax_xent"
    # reference-genre recipe: keep the best-val-accuracy checkpoint
    c.checkpoint.best_metric = "accuracy"
    return c


def _resnet50_imagenet() -> TrainConfig:
    """BASELINE.json:8 — ResNet-50 / ImageNet, DDP all-reduce → data-parallel mesh."""
    c = TrainConfig(preset="resnet50_imagenet")
    c.model = ModelConfig(name="resnet50", num_classes=1000, image_size=224)
    c.data = DataConfig(dataset="imagenet_folder", batch_size=1024, num_workers=16)
    c.optim = OptimConfig(
        name="momentum", learning_rate=0.4, momentum=0.9, weight_decay=1e-4,
        schedule="cosine", warmup_steps=2500, nesterov=False,
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    c.epochs = 90
    c.loss = "softmax_xent"
    # reference-genre recipe: keep the best-val-accuracy checkpoint
    c.checkpoint.best_metric = "accuracy"
    return c


def _vit_b16_imagenet() -> TrainConfig:
    """BASELINE.json:9 — ViT-B/16, bf16 mixed precision + grad accumulation."""
    c = TrainConfig(preset="vit_b16_imagenet")
    c.model = ModelConfig(
        name="vit_b16", num_classes=1000, image_size=224, patch_size=16,
        hidden_size=768, num_layers=12, num_heads=12, mlp_dim=3072,
        dropout_rate=0.1,
    )
    c.data = DataConfig(dataset="imagenet_folder", batch_size=4096, num_workers=16)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-3, weight_decay=0.3, beta2=0.999,
        schedule="cosine", warmup_steps=10000, accum_steps=4, grad_clip_norm=1.0,
        # timm recipe: no decay on bias/norm, nor on cls_token/pos_embed
        # (timm's ViT no_weight_decay() set)
        decay_exclude=r"bias$,scale$,cls_token$,pos_embed$",
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.epochs = 300
    c.loss = "softmax_xent"
    # reference-genre recipe: keep the best-val-accuracy checkpoint
    c.checkpoint.best_metric = "accuracy"
    return c


def _bert_base_mlm() -> TrainConfig:
    """BASELINE.json:10 — BERT-base MLM on Wikipedia, LAMB optimizer."""
    c = TrainConfig(preset="bert_base_mlm")
    c.model = ModelConfig(
        name="bert_base", hidden_size=768, num_layers=12, num_heads=12,
        mlp_dim=3072, vocab_size=30522, max_seq_len=512, dropout_rate=0.1,
    )
    c.data = DataConfig(dataset="text_mlm", batch_size=256, seq_len=512, mlm_prob=0.15)
    c.optim = OptimConfig(
        name="lamb", learning_rate=1.75e-3, weight_decay=0.01,
        schedule="linear", warmup_steps=3125, grad_clip_norm=1.0,
        # BERT recipe's no_decay = ['bias', 'LayerNorm.weight']
        decay_exclude=r"bias$,scale$",
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.total_steps = 28125
    c.loss = "mlm_xent"
    return c


def _llama2_7b() -> TrainConfig:
    """BASELINE.json:11 — Llama-2 7B pretrain; FSDP → GSPMD param sharding."""
    c = TrainConfig(preset="llama2_7b")
    c.model = ModelConfig(
        name="llama", hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=32, mlp_dim=11008, vocab_size=32000, max_seq_len=4096,
        rope_theta=10000.0, rms_norm_eps=1e-5, remat=True,
        # (B,S,V) logits at 32k vocab / 4k seq are ~2 GB fp32 per sample —
        # the fused chunked head (losses.chunked_causal_ce) never builds
        # them; generation clears the flag automatically.
        fused_lm_loss=True,
    )
    c.data = DataConfig(dataset="synthetic_lm", batch_size=128, seq_len=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        decay_exclude=r"scale$",  # no decay on RMSNorm scales (no biases in llama)
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=1, fsdp=-1)
    c.total_steps = 500000
    c.loss = "fused_causal_lm_xent"  # pairs with model.fused_lm_loss above
    return c


def _gpt2_small() -> TrainConfig:
    """GPT-2 124M pretrain (model-zoo extension beyond the BASELINE matrix;
    HF-checkpoint-compatible via interop's 'gpt2' mapping)."""
    c = TrainConfig(preset="gpt2_small")
    c.model = ModelConfig(
        name="gpt2", hidden_size=768, num_layers=12, num_heads=12,
        # 50257 padded to 50304 (2^7·393): the standard GPT-2 trick — the
        # true vocab is indivisible by any power-of-2 mesh, which would
        # silently replicate wte (the largest param) instead of fsdp-
        # sharding it (parallel/partition.py validate_spec fallback).
        mlp_dim=3072, vocab_size=50304, max_seq_len=1024, dropout_rate=0.1,
    )
    c.data = DataConfig(dataset="synthetic_lm", batch_size=64, seq_len=1024)
    c.optim = OptimConfig(
        name="adamw", learning_rate=6e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        decay_exclude=r"bias$,scale$",  # decay only matmul/embedding weights
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    c.total_steps = 600000
    c.loss = "causal_lm_xent"
    return c


def _mixtral_8x7b() -> TrainConfig:
    """Mixtral-8x7B-style sparse-MoE decoder (model-zoo extension): the
    llama family with GShard top-2 routing over 8 experts, GQA (8 kv
    heads), sliding-window attention, and rope_theta=1e6. Mesh splits
    experts over their own axis beside fsdp (SURVEY §2.3 EP)."""
    c = TrainConfig(preset="mixtral_8x7b")
    c.model = ModelConfig(
        name="llama", hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=8, mlp_dim=14336, vocab_size=32000, max_seq_len=4096,
        rope_theta=1e6, rms_norm_eps=1e-5, remat=True, fused_lm_loss=True,
        attention_window=4096,
        num_experts=8, expert_top_k=2, moe_aux_weight=0.02,
    )
    c.data = DataConfig(dataset="synthetic_lm", batch_size=128, seq_len=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        decay_exclude=r"scale$",
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=1, expert=8, fsdp=-1)
    c.total_steps = 500000
    c.loss = "fused_causal_lm_xent"
    return c


def _ling3_flash_lm_ep64() -> TrainConfig:
    """One chip's share of Ling-3.0-flash's language model (inclusionAI,
    https://huggingface.co/inclusionAI/Ling-3.0-flash-VL config.json; the
    vision tower and the MTP head are left out): every width as published;
    the leading dense layer and one period of five KDA layers to one MLA
    layer (42 layers published); 8 of each layer's 512 routed experts, as
    one of 64 expert-parallel chips holds them; an eighth of the 157184-row
    vocabulary. 715 M parameters, 11.4 GB with AdamW's float32 state
    (benchmark/configs/ling3_flash_lm_ep64.json says what was assumed)."""
    c = TrainConfig(preset="ling3_flash_lm_ep64")
    c.model = ModelConfig(
        name="hybrid_lm", hidden_size=2560, num_layers=6, num_heads=32,
        head_dim=128, mlp_dim=6144, vocab_size=19648, max_seq_len=8192,
        rope_theta=6e6, rms_norm_eps=1e-6, remat=True,
        layer_group_size=6, first_dense_layers=1, kv_lora_rank=512,
        rope_head_dim=64, conv_kernel_size=4, kda_gate_lower_bound=-5.0,
        num_experts=512, expert_top_k=8, moe_groups=8, moe_topk_groups=4,
        moe_routed_scale=2.5, moe_mlp_dim=768, experts_held=8,
        experts_held_first=0, expert_capacity_factor=4.0,
    )
    # 4096 synthetic sequences are 33 M tokens (gpt2_small's 51200 x 1024
    # are 52 M); the default 51200 x 8192 took a minute of set-up to draw
    c.data = DataConfig(dataset="synthetic_lm", batch_size=2, seq_len=8192,
                        synthetic_size=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        # decay the matrices and the embedding only: not the norms, the
        # conv taps, the decay's A_log and dt_bias, the router's bias
        decay_exclude=r"scale$,bias$,_conv$,A_log$",
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    # a step of 16384 tokens takes over a second on one v5e: a log every
    # twenty steps, where the presets of faster steps log every fifty
    c.obs.log_every_steps = 20
    c.total_steps = 500000
    c.loss = "causal_lm_xent"
    return c


def _laguna_s_lm_ep32() -> TrainConfig:
    """One chip's share of Laguna-S-2.1's language model (poolside,
    https://huggingface.co/poolside/Laguna-S-2.1 config.json): every width
    as published; layers 0-4 of the 48 (a full-attention layer with the
    dense FFN, three window-512 layers and a full one with expert FFNs:
    the published 3 : 1 after the dense layer), 72 query heads on window
    layers and 48 on full ones over 8 KV heads of 128, a per-head gate,
    YaRN on half a head (full) and plain rope (window); 8 of each layer's
    256 routed experts, as one of 32 expert-parallel chips holds them,
    softmax scores, 10 a token; an eighth of the 100352-row vocabulary.
    811 M parameters, 12.98 GB with AdamW's float32 state
    (benchmark/configs/laguna_s_lm_ep32.json says what was assumed)."""
    c = TrainConfig(preset="laguna_s_lm_ep32")
    c.model = ModelConfig(
        name="hybrid_lm", hidden_size=3072, num_layers=5, num_heads=48,
        num_kv_heads=8, head_dim=128, mlp_dim=12288, vocab_size=12544,
        max_seq_len=8192, rms_norm_eps=1e-6, remat=True,
        layer_kinds=("gqa_full", "gqa_window", "gqa_window", "gqa_window",
                     "gqa_full"),
        layer_heads=(48, 72, 72, 72, 48), attention_window=512,
        rope_theta=5e5, rope_scaling=128.0, rope_scaling_type="yarn",
        rope_original_max_len=8192, rope_beta_fast=32.0, rope_beta_slow=1.0,
        rope_attention_factor=1.4852030263919618, partial_rotary_factor=0.5,
        window_rope_theta=1e4, first_dense_layers=1,
        num_experts=256, expert_top_k=10, moe_score="softmax",
        moe_routed_scale=2.5, moe_mlp_dim=1024, experts_held=8,
        experts_held_first=0, expert_capacity_factor=4.0,
    )
    # 4096 synthetic sequences of 8192 tokens, as the other 8k preset
    c.data = DataConfig(dataset="synthetic_lm", batch_size=1, seq_len=8192,
                        synthetic_size=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        decay_exclude=r"scale$",  # the matrices and the embedding decay
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    c.total_steps = 500000
    c.loss = "causal_lm_xent"
    return c


def _solar_open2_lm_ep40_tp8() -> TrainConfig:
    """One chip's share of Solar-Open2-250B's language model (upstage,
    https://huggingface.co/upstage/Solar-Open2-250B config.json): every
    width as published (hidden 4096, heads of 128, experts and the shared
    expert of 1280, a 320-wide softmax router, 8 a token, conv 4); layers
    0-3 of the 48, one whole period: grouped-query attention with NO
    rotation and a gate a channel, then three KDA layers whose decay gate
    is the report's unbounded softplus, step sizes up to 2, gates through
    128 features; every layer an expert layer (no leading dense one). Each
    layer is shared by 40 chips: 8 of its 320 experts here (expert
    parallelism over 40), 8 of its 64 query/KDA heads and 1 of its 8 KV
    heads (tensor parallelism in groups of 8), an eighth of the 196608-row
    vocabulary. 840.8 M parameters, 13.45 GB with AdamW's float32 state
    (benchmark/configs/solar_open2_lm_ep40_tp8.json says what was
    assumed)."""
    c = TrainConfig(preset="solar_open2_lm_ep40_tp8")
    c.model = ModelConfig(
        name="hybrid_lm", hidden_size=4096, num_layers=4, num_heads=64,
        num_kv_heads=8, head_dim=128, vocab_size=24576, max_seq_len=8192,
        rms_norm_eps=1e-5, remat=True,
        layer_kinds=("gqa_full", "kda", "kda", "kda"),
        partial_rotary_factor=0.0,  # `use_rope: false`
        heads_held=8, heads_held_first=0,
        kda_gate="softplus", kda_beta_scale=2.0, kda_gate_rank=128,
        kda_out_gate="channel", gqa_out_gate="channel", conv_kernel_size=4,
        first_dense_layers=0, num_experts=320, expert_top_k=8,
        moe_score="softmax", moe_routed_scale=1.0, moe_mlp_dim=1280,
        experts_held=8, experts_held_first=0, expert_capacity_factor=4.0,
    )
    # 4096 synthetic sequences of 8192 tokens, as the other 8k presets
    c.data = DataConfig(dataset="synthetic_lm", batch_size=1, seq_len=8192,
                        synthetic_size=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        # decay the matrices and the embedding only: not the norms, the
        # conv taps, the decay's A_log and dt_bias
        decay_exclude=r"scale$,bias$,_conv$,A_log$",
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    c.total_steps = 500000
    c.loss = "causal_lm_xent"
    return c


def _kanana2_lm_ep8() -> TrainConfig:
    """One chip's share of Kanana-2-30B-A3B's language model (kakaocorp,
    https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601
    config.json, ``model_type: deepseek_v3``): every width as published
    (hidden 2048, 32 heads, latent attention over a 512-wide latent with
    128 plain + 64 rotated score dims and 128-deep values, a dense SwiGLU
    of 6144, experts of 768, two shared experts = one SwiGLU of 1536, a
    128-wide ungrouped sigmoid router, 6 a token, scale 2.448); layers 0-5
    of the 48: the leading dense layer and five expert layers, EVERY layer
    latent attention in DeepSeek-V3's plain form (no head norms, no gate,
    the rotated dims in pairs (2i, 2i+1), theta 1e6). Each layer is shared
    by 8 chips by expert parallelism: 16 of its 128 routed experts here,
    an eighth of the 128256-row vocabulary, every head whole. The
    selection bias moves by the family's balancing rule at 0.001 a step
    (``noaux_tc``; arXiv:2412.19437 4.2). 687.5 M parameters, 11.0 GB with
    AdamW's float32 state (benchmark/configs/kanana2_lm_ep8.json says what
    was assumed)."""
    c = TrainConfig(preset="kanana2_lm_ep8")
    c.model = ModelConfig(
        name="hybrid_lm", hidden_size=2048, num_layers=6, num_heads=32,
        head_dim=128, mlp_dim=6144, vocab_size=16032, max_seq_len=8192,
        rope_theta=1e6, rms_norm_eps=1e-6, remat=True,
        layer_kinds=("mla",) * 6, kv_lora_rank=512, rope_head_dim=64,
        mla_qk_norm=False, mla_out_gate="none", mla_rope="pairs",
        first_dense_layers=1, num_experts=128, expert_top_k=6,
        moe_groups=1, moe_topk_groups=1, moe_routed_scale=2.448,
        moe_mlp_dim=768, moe_shared_mlp_dim=1536, moe_bias_rate=1e-3,
        experts_held=16, experts_held_first=0, expert_capacity_factor=4.0,
    )
    # 4096 synthetic sequences of 8192 tokens, as the other 8k presets
    c.data = DataConfig(dataset="synthetic_lm", batch_size=2, seq_len=8192,
                        synthetic_size=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        # decay the matrices and the embedding only: not the norms, nor
        # the router's bias (which the optimizer does not move at all)
        decay_exclude=r"scale$,bias$",
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    # a step of 16384 tokens takes most of a second on one v5e: a log
    # every twenty steps, as the first hybrid preset's
    c.obs.log_every_steps = 20
    c.total_steps = 500000
    c.loss = "causal_lm_xent"
    return c


def _lfm2_8b_a1b_lm_ep4() -> TrainConfig:
    """One chip's share of LFM2-8B-A1B's language model (LiquidAI,
    https://huggingface.co/LiquidAI/LFM2-8B-A1B config.json, ``model_type:
    lfm2_moe``): every width as published (hidden 2048, a gated short
    convolution of 3 taps a channel, 32 query heads over 8 KV heads of 64
    with an RMSNorm over each head's q and k and no output gate, a dense
    SwiGLU of 7168, experts of 1792, a 32-wide ungrouped sigmoid router, 4
    a token, NO shared expert, rope theta 1e6, eps 1e-5, the head tied to
    the input table); layers 1-5 of the 24: one of the two leading dense
    layers, then one whole period ``full_attention, conv, conv, conv`` of
    expert layers. Each layer is shared by 4 chips by expert parallelism:
    8 of its 32 routed experts here, a quarter of the 65536-row
    vocabulary, every head and channel whole. The selection bias moves by
    DeepSeek-V3's balancing rule at 0.001 a step. 507.8 M parameters,
    8.13 GB with AdamW's float32 state
    (benchmark/configs/lfm2_8b_a1b_lm_ep4.json says what was assumed)."""
    c = TrainConfig(preset="lfm2_8b_a1b_lm_ep4")
    c.model = ModelConfig(
        name="hybrid_lm", hidden_size=2048, num_layers=5, num_heads=32,
        num_kv_heads=8, head_dim=64, mlp_dim=7168, vocab_size=16384,
        max_seq_len=8192, rope_theta=1e6, rms_norm_eps=1e-5, remat=True,
        layer_kinds=("conv", "gqa_full", "conv", "conv", "conv"),
        conv_kernel_size=3, gqa_qk_norm=True, gqa_out_gate="none",
        tie_word_embeddings=True, first_dense_layers=1,
        num_experts=32, expert_top_k=4, moe_groups=1, moe_topk_groups=1,
        moe_routed_scale=1.0, moe_mlp_dim=1792, moe_shared_mlp_dim=-1,
        moe_bias_rate=1e-3, experts_held=8, experts_held_first=0,
        # a token meets ONE held expert on average (4 x 8 / 32), so 4.0
        # would be the worst case itself (65536 rows): twice the mean's
        # 16384 (the configuration file's `assumed` gives the readings)
        expert_capacity_factor=2.0,
    )
    # 4096 synthetic sequences of 8192 tokens, as the other 8k presets
    c.data = DataConfig(dataset="synthetic_lm", batch_size=2, seq_len=8192,
                        synthetic_size=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        # decay the matrices and the table only: not the norms, the conv
        # taps, nor the router's bias (which the optimizer does not move)
        decay_exclude=r"scale$,bias$,taps$",
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    # (a step of 16384 tokens takes a third of a second on one v5e: the
    # default log every fifty steps, as the presets of like steps)
    c.total_steps = 500000
    c.loss = "causal_lm_xent"
    return c


def _mellum2_12b_a2_5b_lm_ep4() -> TrainConfig:
    """One chip's share of Mellum2-12B-A2.5B's language model (JetBrains,
    https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct config.json,
    ``model_type: mellum``) in a long-context stage at 16384 tokens: every
    width as published (hidden 2304, 32 query heads over 4 KV heads of 128
    with an RMSNorm over each head's q and k and no output gate, a window
    of 1024 on the sliding layers, experts of 896, a 64-wide softmax
    router, 8 a token renormalised over the eight, NO shared expert and no
    dense layer, eps 1e-6, an untied head); layers 0-3 of the 28: one
    whole period ``sliding, sliding, sliding, full``. The sliding layers
    turn by plain rope at theta 5e5, the full layer by YaRN at factor 16
    from 8192 original positions over the whole head, so half of a
    sequence's positions lie past the original length. Each layer is shared
    by 4 chips by expert parallelism: 16 of its 64 routed experts here, a
    quarter of the 98304-row vocabulary, every head whole. 595.2 M
    parameters, 9.52 GB with AdamW's float32 state
    (benchmark/configs/mellum2_12b_a2_5b_lm_ep4.json says what was
    assumed)."""
    c = TrainConfig(preset="mellum2_12b_a2_5b_lm_ep4")
    c.model = ModelConfig(
        name="hybrid_lm", hidden_size=2304, num_layers=4, num_heads=32,
        num_kv_heads=4, head_dim=128, vocab_size=24576, max_seq_len=16384,
        mlp_dim=7168,  # published; every layer is sparse, so none reads it
        rms_norm_eps=1e-6, remat=True,
        layer_kinds=("gqa_window", "gqa_window", "gqa_window", "gqa_full"),
        attention_window=1024, rope_theta=5e5, window_rope_theta=5e5,
        rope_scaling=16.0, rope_scaling_type="yarn",
        rope_original_max_len=8192, rope_beta_fast=32.0, rope_beta_slow=1.0,
        rope_attention_factor=1.2772588722239782, partial_rotary_factor=1.0,
        gqa_qk_norm=True, gqa_out_gate="none", first_dense_layers=0,
        num_experts=64, expert_top_k=8, moe_score="softmax",
        moe_routed_scale=1.0, moe_mlp_dim=896, moe_shared_mlp_dim=-1,
        moe_bias_rate=0.0, experts_held=16, experts_held_first=0,
        # a token meets TWO held experts on average (8 x 16 / 64), so 4.0
        # would be the worst case itself (8 N rows). 1.25 holds for weights
        # that route EVENLY, as a trained model's entering a long-context
        # stage do (read on the chip: the configuration file's `assumed`).
        # It does NOT hold from models/hybrid.py's own flat N(0, 0.02) init
        # at this length: the routers collapse behind the attention mixers
        # and 5-25 % of the steps pass the bound and are skipped, at 1.5
        # too, at 2.0 still one step in 200 (PERF.md section 6, PR 48;
        # ROADMAP R15). From scratch, raise it with
        # --set model.expert_capacity_factor=... (4.0 cannot be passed)
        expert_capacity_factor=1.25,
    )
    # 4096 synthetic sequences of 16384 tokens: 4096 steps an epoch
    c.data = DataConfig(dataset="synthetic_lm", batch_size=1, seq_len=16384,
                        synthetic_size=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        decay_exclude=r"scale$",  # the matrices and the embedding decay
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    c.total_steps = 500000
    c.loss = "causal_lm_xent"
    return c


def _ouro_2_6b_lm_l8() -> TrainConfig:
    """One pipeline stage of Ouro-2.6B (ByteDance,
    https://huggingface.co/ByteDance/Ouro-2.6B config.json; arXiv:2510.25741):
    every width as published (hidden 2048, 16 heads over 16 KV heads of 128,
    SwiGLU of 5632, the whole vocabulary of 49152 with an untied head, rope
    theta 1e6, RMSNorm eps 1e-6), `total_ut_steps` 4 passes over the same
    weights, a sandwich of four norms a layer, a head and an exit gate at
    every pass; 8 of the 48 layers. 612 438 017 parameters, 9.80 GB with
    AdamW's float32 state (benchmark/configs/ouro_2_6b_lm_l8.json says what
    was assumed)."""
    c = TrainConfig(preset="ouro_2_6b_lm_l8")
    c.model = ModelConfig(
        name="llama", hidden_size=2048, num_layers=8, num_heads=16,
        num_kv_heads=16, mlp_dim=5632, vocab_size=49152, max_seq_len=4096,
        rope_theta=1e6, rms_norm_eps=1e-6, remat=True,
        loop_steps=4, sandwich_norm=True, loop_entropy_beta=0.05,
    )
    # 4096 synthetic sequences of 4096 tokens: 4096 steps an epoch
    c.data = DataConfig(dataset="synthetic_lm", batch_size=1, seq_len=4096,
                        synthetic_size=4096)
    c.optim = OptimConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1, beta2=0.95,
        schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        decay_exclude=r"scale$,bias$",  # matrices (the gate's too) and embedding decay
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    c.total_steps = 500000
    c.loss = "looped_lm_xent"
    return c


def _t5_small() -> TrainConfig:
    """T5-small seq2seq pretrain (model-zoo extension beyond the BASELINE
    matrix). HF-layout-compatible via interop's 't5' mapping
    (feed_forward_proj='relu'); trains an UNTIED head — to load published
    tied v1.0 checkpoints set model.tie_word_embeddings=true."""
    c = TrainConfig(preset="t5_small")
    c.model = ModelConfig(
        name="t5", hidden_size=512, num_layers=6, decoder_layers=6,
        num_heads=8, mlp_dim=2048, vocab_size=32128, max_seq_len=512,
        dropout_rate=0.1,
    )
    c.data = DataConfig(dataset="synthetic_seq2seq", batch_size=128,
                        seq_len=512, tgt_seq_len=128)
    c.optim = OptimConfig(
        # The T5 paper trains with Adafactor; inverse-sqrt decay is
        # approximated with cosine here (the schedule families in
        # optim.make_schedule).
        name="adafactor", learning_rate=1e-2, weight_decay=0.0,
        schedule="cosine", warmup_steps=10000, grad_clip_norm=1.0,
    )
    c.precision = PrecisionConfig(compute_dtype="bfloat16")
    c.mesh = MeshConfig(data=-1)
    c.total_steps = 500000
    c.loss = "seq2seq_xent"
    return c


_PRESETS = {
    "resnet18_cifar10": _resnet18_cifar10,
    "resnet50_imagenet": _resnet50_imagenet,
    "vit_b16_imagenet": _vit_b16_imagenet,
    "bert_base_mlm": _bert_base_mlm,
    "llama2_7b": _llama2_7b,
    "gpt2_small": _gpt2_small,
    "t5_small": _t5_small,
    "mixtral_8x7b": _mixtral_8x7b,
    "ling3_flash_lm_ep64": _ling3_flash_lm_ep64,
    "laguna_s_lm_ep32": _laguna_s_lm_ep32,
    "ouro_2_6b_lm_l8": _ouro_2_6b_lm_l8,
    "solar_open2_lm_ep40_tp8": _solar_open2_lm_ep40_tp8,
    "kanana2_lm_ep8": _kanana2_lm_ep8,
    "lfm2_8b_a1b_lm_ep4": _lfm2_8b_a1b_lm_ep4,
    "mellum2_12b_a2_5b_lm_ep4": _mellum2_12b_a2_5b_lm_ep4,
}


def list_presets() -> list[str]:
    return sorted(_PRESETS)


def get_preset(name: str) -> TrainConfig:
    if name not in _PRESETS:
        raise KeyError(f"unknown preset {name!r}; have {list_presets()}")
    return _PRESETS[name]()
