"""Per-layer metric `step_head_loss_ms.tokens`: device milliseconds a step in
the head and the loss, forward and backward together (`lm_head`,
`exit_head`, `jvp(loss)`, `transpose(jvp(loss))`); see scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "phase", "head_loss")
