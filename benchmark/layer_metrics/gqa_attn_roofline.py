"""Per-layer metric `gqa_attn_roofline`: as `swa_attn_roofline`, for the
full-attention layers (the events `gqa_kernel_pattern` names; causal pairs
S (S + 1) / 2 at those layers' query heads over the KV heads)."""

import gqa_flops


def read(ctx):
    return gqa_flops.roofline_share(ctx, "gqa_kernel_pattern",
                                    "full_attention")
