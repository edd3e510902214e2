"""Per-layer metric `step_forward_ms.tokens`: device milliseconds a step in the
forward pass's instructions (phase `forward`: `jvp(forward)` outside the
head); see scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "phase", "forward")
