"""Per-layer metric `step_ffn_ms.tokens`: device milliseconds a step in the
dense feed-forward blocks over forward, backward and recompute; see
scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "component", "ffn")
