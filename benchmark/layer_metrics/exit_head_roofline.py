"""Per-layer metric `exit_head_roofline`: the least time the chip could take
for the two products the head's kernels compute at the exits of a looped
decoder (loop_flops.py: 2 x 2 x N x V x C an exit, `total_ut_steps` exits;
XLA's dX product is outside the kernels' events and outside the count) over
the time the trace shows for those events (`head_kernel_pattern`). None
where the configuration has no loop or names no such kernel, or the trace
holds none of its events."""

import loop_flops


def read(ctx):
    return loop_flops.roofline_share(
        ctx, "head_kernel_pattern", loop_flops.exit_head_cost(
            ctx["config"], ctx["cell"], ctx["chips"]))
