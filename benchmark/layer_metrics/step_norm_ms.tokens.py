"""Per-layer metric `step_norm_ms.tokens`: device milliseconds a step in the
norms outside a mixer over forward, backward and recompute; see
scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "component", "norm")
