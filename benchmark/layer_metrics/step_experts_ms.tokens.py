"""Per-layer metric `step_experts_ms.tokens`: device milliseconds a step in the
expert layers whole over forward, backward and recompute: router, dispatch,
grouped products, combine, shared expert; see scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "component", "experts")
