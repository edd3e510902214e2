"""Regex partition rules: param-name path → PartitionSpec.

The TPU-native replacement for FSDP's FlatParameter sharding
(torch:distributed/fsdp/_flat_param.py:202) and tensor-parallel module styles
(torch:distributed/tensor/parallel/style.py): instead of wrapping modules,
we map each parameter's pytree path through an ordered list of
``(regex, PartitionSpec)`` rules (the GSPMD idiom — SURVEY C13, SNIPPETS §[2]
pattern). XLA then inserts the all-gathers / reduce-scatters that FSDP's
runtime performed by hand.

Rules are matched against '/'-joined flax param paths, e.g.
``params/encoder/layers_3/attn/q_proj/kernel``. First match wins; scalars are
always replicated; a catch-all ``.*`` rule should end every rule set.
"""

from __future__ import annotations

import re
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec


class PartitionRules:
    """Ordered (regex, PartitionSpec) table applied to a params pytree."""

    def __init__(self, rules: list[tuple[str, PartitionSpec]]):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(self, name: str, shape: tuple[int, ...]) -> PartitionSpec:
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        for pat, spec in self.rules:
            if pat.search(name):
                return spec
        raise ValueError(f"no partition rule matched param {name!r} (shape {shape})")

    def tree_specs(self, params: Any) -> Any:
        """Pytree of PartitionSpec matching ``params``' structure."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        specs = [
            self.spec_for(path_name(p), getattr(leaf, "shape", ()))
            for p, leaf in flat
        ]
        return jax.tree_util.tree_unflatten(treedef, specs)

    def tree_shardings(self, mesh: Mesh, params: Any) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        shardings = []
        for p, leaf in flat:
            shape = getattr(leaf, "shape", ())
            spec = self.spec_for(path_name(p), shape)
            spec = validate_spec(spec, shape, mesh)
            shardings.append(NamedSharding(mesh, spec))
        return jax.tree_util.tree_unflatten(treedef, shardings)


def validate_spec(spec: PartitionSpec, shape: tuple[int, ...], mesh: Mesh
                  ) -> PartitionSpec:
    """Drop sharding on dims the mesh can't divide evenly.

    GSPMD requires dim % (product of assigned axis sizes) == 0; real models
    always have stray dims (num_classes=10, vocab remainders) that a generic
    rule can't shard on every mesh — fall back to replicating THAT dim only,
    which is exactly what FSDP's pad-to-divisible flat-param avoids at the
    cost of padding (we prefer replication: these dims are small).
    Also truncates specs longer than the array rank (a 2-d rule matched
    against a reshaped scalar etc.)."""
    entries = list(spec)
    out = []
    for i, entry in enumerate(entries[: len(shape)]):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(entry if shape[i] % size == 0 else None)
    return PartitionSpec(*out)


def replication_fallback_dims(spec: PartitionSpec, shape: tuple[int, ...],
                              sizes: dict[str, int]) -> list[int]:
    """Dims of ``shape`` that a mesh with the given axis sizes could NOT
    shard as ``spec`` asks — ``validate_spec`` would replicate them.

    The dict-of-sizes twin of ``validate_spec``: the elastic-reshard
    feasibility question ("can this checkpoint restore onto mesh X?",
    tools/ckpt_inspect.py --mesh) must be answerable WITHOUT
    constructing a jax Mesh, whose device grid needs the target
    machine's actual devices."""
    out = []
    for i, entry in enumerate(list(spec)[: len(shape)]):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([sizes.get(a, 1) for a in axes]))
        if size > 1 and shape[i] % size != 0:
            out.append(i)
    return out


def path_name(path) -> str:
    """'/'-joined readable name for a jax key path."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def match_partition_rules(rules: list[tuple[str, PartitionSpec]], params: Any) -> Any:
    """Functional one-shot form (SNIPPETS §[2] pattern, reimplemented)."""
    return PartitionRules(rules).tree_specs(params)


def grad_buckets(params: Any, bucket_bytes: int) -> list[list[int]]:
    """Gradient buckets for the overlapped-collectives path
    (steps.overlap_grad_reducer) — the layout half of DDP's reducer
    (torch reducer.hpp:285 / ``bucket_cap_mb``).

    Flattened-leaf indices grouped in REVERSE parameter order (backward
    produces grads output-end first, so the last layers' buckets close —
    and their collectives launch — while earlier layers still compute),
    each bucket closing once its cumulative byte size reaches
    ``bucket_bytes``. Works on arrays or ShapeDtypeStructs (AOT
    bucketing from an eval_shape tree, no materialized params needed).
    Invariants the tests pin: every leaf appears in exactly one bucket;
    concatenating the buckets yields exactly ``reversed(range(n))``;
    every bucket except possibly the last meets the byte floor."""
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be > 0, got {bucket_bytes}")
    leaves = jax.tree_util.tree_leaves(params)
    buckets: list[list[int]] = []
    cur: list[int] = []
    size = 0
    for i in reversed(range(len(leaves))):
        leaf = leaves[i]
        cur.append(i)
        size += int(np.prod(getattr(leaf, "shape", ()) or (1,))) * \
            np.dtype(leaf.dtype).itemsize
        if size >= bucket_bytes:
            buckets.append(cur)
            cur, size = [], 0
    if cur:
        buckets.append(cur)
    return buckets


# ------------------------------------------------------------------ rule sets
#
# Sharding recipes per model family. Convention on axis use:
#   'fsdp'   — ZeRO-style weight sharding; shard the LARGEST dim that is not
#              already tensor-sharded, so reshards are cheap.
#   'tensor' — megatron TP: column-parallel on q/k/v/up projections
#              (output dim), row-parallel on o/down projections (input dim).
# Biases/norm scales replicate. The optimizer state inherits these specs
# through jit's sharding propagation (SURVEY C13 rightmost column).


def dense_rules() -> list[tuple[str, PartitionSpec]]:
    """Fallback for unregistered models: shard kernels on their output
    channel (conv HWIO dim 3; dense (in,out) dim 1) over 'fsdp'; replicate
    the rest. Conv rule must precede the generic kernel rule — regex can't
    see array rank."""
    return [
        (r"conv[^/]*/kernel$", P(None, None, None, "fsdp")),
        (r"(kernel|embedding)$", P(None, "fsdp")),
        (r".*", P()),
    ]


def llama_rules() -> list[tuple[str, PartitionSpec]]:
    """Llama-2: FSDP × TP layout (BASELINE.json:11).

    Matches flax param paths from models/llama.py.
    """
    return [
        # Embedding: vocab × hidden — VOCAB over 'fsdp', hidden unsharded.
        # This is the GSPMD-friendly gather layout: a vocab-sharded table
        # lowers to masked-gather + psum over 'fsdp' and the output
        # inherits the token indices' (batch, seq) sharding directly.
        # Sharding hidden instead (or vocab over 'tensor', the tied-weight
        # layout) left the partitioner resharding the gather output via
        # "involuntary full rematerialization" under tp×cp meshes
        # (observed in dryrun_multichip).
        (r"tok_embed/embedding$", P("fsdp", None)),
        # Attention: hidden × (heads·head_dim)
        (r"(q_proj|k_proj|v_proj)/kernel$", P("fsdp", "tensor")),
        (r"o_proj/kernel$", P("tensor", "fsdp")),
        # MoE experts (leading E dim over 'expert'); router replicated.
        # Must precede the dense-MLP rules — same projection names.
        (r"experts/(gate_proj|up_proj)/kernel$", P("expert", "fsdp", "tensor")),
        (r"experts/down_proj/kernel$", P("expert", "tensor", "fsdp")),
        (r"router/kernel$", P()),
        # MLP: gate/up column-parallel, down row-parallel
        (r"(gate_proj|up_proj)/kernel$", P("fsdp", "tensor")),
        (r"down_proj/kernel$", P("tensor", "fsdp")),
        # Final LM head
        (r"lm_head/kernel$", P("fsdp", "tensor")),
        # Norm scales replicate (four a layer under sandwich_norm), and the
        # looped decoder's exit gate (d x 1 and a bias)
        (r"(input_norm|attn_out_norm|post_attn_norm|mlp_out_norm|final_norm)"
         r"/scale$", P()),
        (r"exit_gate/(kernel|bias)$", P()),
        (r".*", P()),
    ]


def llama_pp_rules() -> list[tuple[str, PartitionSpec]]:
    """Pipelined Llama (models/pipeline_lm.py): block params carry a leading
    stacked-layer dim sharded over 'stage'; within a layer the FSDP×TP layout
    matches llama_rules. Embed/head live outside the pipeline (replicated
    over 'stage', sharded over fsdp/tensor as usual)."""
    return [
        # Interleaved-schedule storage (C, S, Lps, ...): stage on dim 1.
        (r"blocks_csl/.*(q_proj|k_proj|v_proj)/kernel$",
         P(None, "stage", None, "fsdp", "tensor")),
        (r"blocks_csl/.*o_proj/kernel$",
         P(None, "stage", None, "tensor", None, "fsdp")),
        (r"blocks_csl/.*experts/(gate_proj|up_proj)/kernel$",
         P(None, "stage", None, "expert", "fsdp", "tensor")),
        (r"blocks_csl/.*experts/down_proj/kernel$",
         P(None, "stage", None, "expert", "tensor", "fsdp")),
        (r"blocks_csl/.*router/kernel$", P(None, "stage")),
        (r"blocks_csl/.*(gate_proj|up_proj)/kernel$",
         P(None, "stage", None, "fsdp", "tensor")),
        (r"blocks_csl/.*down_proj/kernel$",
         P(None, "stage", None, "tensor", "fsdp")),
        (r"blocks_csl/.*scale$", P(None, "stage")),
        # GPipe/1F1B storage (L, ...): stage on dim 0.
        (r"blocks/.*(q_proj|k_proj|v_proj)/kernel$",
         P("stage", "fsdp", "tensor")),
        (r"blocks/.*o_proj/kernel$", P("stage", "tensor", None, "fsdp")),
        # MoE experts: (L, E, ...) — stage on layers, expert on experts.
        # Must precede the dense-MLP rules (same projection names).
        (r"blocks/.*experts/(gate_proj|up_proj)/kernel$",
         P("stage", "expert", "fsdp", "tensor")),
        (r"blocks/.*experts/down_proj/kernel$",
         P("stage", "expert", "tensor", "fsdp")),
        (r"blocks/.*router/kernel$", P("stage")),
        (r"blocks/.*(gate_proj|up_proj)/kernel$", P("stage", "fsdp", "tensor")),
        (r"blocks/.*down_proj/kernel$", P("stage", "tensor", "fsdp")),
        (r"blocks/.*scale$", P("stage")),
        # vocab over 'fsdp' — same gather-friendly layout as llama_rules
        (r"tok_embed/embedding$", P("fsdp", None)),
        (r"lm_head/kernel$", P("fsdp", "tensor")),
        (r".*", P()),
    ]


def hybrid_rules() -> list[tuple[str, PartitionSpec]]:
    """models/hybrid.py: the llama recipe over the new tree. Projections
    keep heads on 'tensor' ((D, H, d) kernels, (H, d, D) for the outputs;
    the grouped-query kinds' modules are `gqa` and `swa`, their K and V
    kernels carry the KV heads);
    the held experts' stacked kernels put their leading dim on 'expert';
    the shared expert is a dense SwiGLU at a width of its own
    (`model.moe_shared_mlp_dim`: the plain FFN rows below carry it);
    a gate a CHANNEL carries heads too (`gc_proj`, (D or r, H, d)), and
    where it or the decay's `a_proj` goes through a low rank, the first
    factor (`gc_down`, `a_down`: (D, r), what every chip of a `tensor` group
    computes alike) replicates;
    the `conv` mixer's two projections put their rows on 'fsdp' alone (no
    share of its channels over 'tensor' yet: ROADMAP Reach);
    the latent down-projections, the router, the conv taps, A_log, dt_bias,
    the head gates and every norm replicate (small, or per-head vectors).
    A model told its share of heads (`model.heads_held`) is ONE chip's view
    of `tensor`: its leaves are already that axis's shards."""
    return [
        (r"tok_embed/embedding$", P("fsdp", None)),
        (r"kda/(q_proj|k_proj|v_proj|a_proj)/kernel$",
         P("fsdp", "tensor", None)),
        (r"mla/(q_proj|kv_up)/kernel$", P("fsdp", "tensor", None)),
        (r"(gqa|swa)/(q_proj|k_proj|v_proj)/kernel$",
         P("fsdp", "tensor", None)),
        (r"(kda|gqa|swa)/gc_proj/kernel$", P("fsdp", "tensor", None)),
        (r"kda/(a_down|gc_down)/kernel$", P()),
        (r"(kda|mla|gqa|swa)/o_proj/kernel$", P("tensor", None, "fsdp")),
        (r"mla/(kv_down|k_rope_proj)/kernel$", P("fsdp", None)),
        (r"conv/(in_proj|out_proj)/kernel$", P("fsdp", None)),
        (r"experts/(gate_proj|up_proj)/kernel$",
         P("expert", "fsdp", "tensor")),
        (r"experts/down_proj/kernel$", P("expert", "tensor", "fsdp")),
        (r"router/(kernel|bias)$", P()),
        (r"(gate_proj|up_proj)/kernel$", P("fsdp", "tensor")),
        (r"down_proj/kernel$", P("tensor", "fsdp")),
        (r"lm_head/kernel$", P("fsdp", "tensor")),
        (r".*", P()),
    ]


def gpt2_rules() -> list[tuple[str, PartitionSpec]]:
    """GPT-2: FSDP × TP. Tied head means the vocab-over-'fsdp' embedding is
    also the output projection; the logsumexp then reduces over 'fsdp'."""
    return [
        (r"wte/embedding$", P("fsdp", None)),
        (r"wpe$", P()),
        (r"(q_proj|k_proj|v_proj)/kernel$", P("fsdp", "tensor")),
        (r"attn/c_proj/kernel$", P("tensor", None, "fsdp")),
        (r"c_fc/kernel$", P("fsdp", "tensor")),
        (r"c_proj/kernel$", P("tensor", "fsdp")),
        (r".*", P()),
    ]


def bert_rules() -> list[tuple[str, PartitionSpec]]:
    return [
        (r"(word_embed|pos_embed|type_embed)/embedding$", P(None, "fsdp")),
        (r"(query|key|value)/kernel$", P("fsdp", "tensor")),
        (r"attn_out/kernel$", P("tensor", "fsdp")),
        (r"mlp_in/kernel$", P("fsdp", "tensor")),
        (r"mlp_out/kernel$", P("tensor", "fsdp")),
        (r"(mlm_dense|pooler)/kernel$", P("fsdp", None)),
        (r".*", P()),
    ]


def vit_rules() -> list[tuple[str, PartitionSpec]]:
    return [
        (r"patch_embed/kernel$", P(None, None, None, "fsdp")),
        (r"(query|key|value)/kernel$", P("fsdp", "tensor")),
        (r"attn_out/kernel$", P("tensor", "fsdp")),
        (r"mlp_in/kernel$", P("fsdp", "tensor")),
        (r"mlp_out/kernel$", P("tensor", "fsdp")),
        (r"head/kernel$", P("fsdp", None)),
        (r".*", P()),
    ]


def resnet_rules() -> list[tuple[str, PartitionSpec]]:
    """ResNets are small — replicate params (DDP-equivalent), shard only batch.
    With fsdp>1 conv kernels shard on output channels (HWIO last dim)."""
    return [
        (r"conv[^/]*/kernel$", P(None, None, None, "fsdp")),
        (r"fc/kernel$", P(None, "fsdp")),
        (r".*", P()),
    ]


def t5_rules() -> list[tuple[str, PartitionSpec]]:
    """T5 encoder-decoder (models/t5.py): the llama FSDP×TP recipe applied
    to both stacks — q/k/v column-parallel over 'tensor', o row-parallel,
    MLP wi/wo likewise; the shared embedding vocab-sharded over 'fsdp'
    (same gather-layout rationale as llama's tok_embed rule); relative-
    bias tables and norm scales replicate (tiny)."""
    return [
        (r"shared/embedding$", P("fsdp", None)),
        (r"(q_proj|k_proj|v_proj)/kernel$", P("fsdp", "tensor")),
        (r"o_proj/kernel$", P("tensor", None, "fsdp")),
        (r"mlp/wi/kernel$", P("fsdp", "tensor")),
        (r"mlp/wo/kernel$", P("tensor", "fsdp")),
        (r"lm_head/kernel$", P("fsdp", "tensor")),
        (r"rel_bias/embedding$", P()),
        (r".*", P()),
    ]


_RULE_SETS: dict[str, Callable[[], list[tuple[str, PartitionSpec]]]] = {
    "resnet": resnet_rules,
    "vit": vit_rules,
    "bert": bert_rules,
    "gpt": gpt2_rules,
    "llama_pp": llama_pp_rules,  # must precede the "llama" prefix match
    "llama": llama_rules,
    "hybrid": hybrid_rules,
    "t5": t5_rules,
    "dense": dense_rules,
}


def rules_for_model(model_name: str) -> PartitionRules:
    # LoRA adapter leaves (lora.py) replicate: rank-r matrices are tiny
    # (d*r vs d*d), and replication keeps the A@B fold free of collectives
    # inside the merged train step. Prepended so the family rule sets'
    # generic `kernel` patterns can never capture them.
    lora_rules = [(r"lora_[ab]$", P())]
    for prefix, fn in _RULE_SETS.items():
        if model_name.startswith(prefix):
            return PartitionRules(lora_rules + fn())
    return PartitionRules(lora_rules + dense_rules())
