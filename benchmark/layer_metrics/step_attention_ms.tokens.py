"""Per-layer metric `step_attention_ms.tokens`: device milliseconds a step in
the mixer modules whole over forward, backward and recompute: projections,
rotation, gates and the kernels; see scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "component", "attention")
