"""Per-layer metric `step_backward_ms.tokens`: device milliseconds a step in
the backward pass's instructions (phase `backward`:
`transpose(jvp(forward))` outside `rematted_computation`); see
scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "phase", "backward")
