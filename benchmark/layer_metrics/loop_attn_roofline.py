"""Per-layer metric `loop_attn_roofline`: the least time the chip could take
for one step's attention calls of a looped decoder (loop_flops.py: causal
pairs S (S + 1) / 2 at the configuration's heads, forward and backward once
a (pass, layer) application, `total_ut_steps` x `num_hidden_layers` of
them) over the time the trace shows for the flash kernel's events
(`flash_kernel_pattern`; the forward that activation checkpointing runs
again included). None where the configuration has no loop or names no
kernel, or the trace holds none of its events."""

import loop_flops


def read(ctx):
    return loop_flops.roofline_share(
        ctx, "flash_kernel_pattern", loop_flops.loop_attention_cost(
            ctx["config"], ctx["cell"], ctx["chips"]))
