"""Per-layer metric `step_optimizer_ms.tokens`: device milliseconds a step in
the optimizer's instructions (scope `optimizer`: the clip, AdamW, the skip-
select); see scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "phase", "optimizer")
