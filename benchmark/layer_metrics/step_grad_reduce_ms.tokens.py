"""Per-layer metric `step_grad_reduce_ms.tokens`: device milliseconds a step in
the gradient's reduction: scope `grad_reduce`, the backward pass's reducing
collectives and the fusions that hold one; see scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "phase", "grad_reduce")
