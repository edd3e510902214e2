"""Per-layer metric `swa_attn_roofline`: the least time the chip could take
for one step's window-attention calls (gqa_flops.py: only the band's pairs,
sum_i min(i + 1, window), at the window layers' query heads over the KV
heads, forward and backward once a window layer) over the time the trace
shows for the kernel's events (`swa_kernel_pattern`; the forward that
activation checkpointing runs again included). None where the configuration
names no such kernel, lists no layer types, or the trace holds none of its
events."""

import gqa_flops


def read(ctx):
    return gqa_flops.roofline_share(ctx, "swa_kernel_pattern",
                                    "sliding_attention")
