"""Per-layer metric `step_recompute_ms.tokens`: device milliseconds a step in
what `model.remat` runs again (every instruction under
`rematted_computation`); see scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "phase", "recompute")
