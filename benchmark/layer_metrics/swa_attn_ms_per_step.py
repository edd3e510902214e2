"""Per-layer metric `swa_attn_ms_per_step`: device milliseconds a step of
the window layers' attention kernel (the events the configuration's
`swa_kernel_pattern` names: forward, the forward that activation
checkpointing runs again, dQ, dK/dV, a window layer). None where the
configuration names no such kernel or the trace holds none of its events."""

import readers


def read(ctx):
    found = readers.kernel_seconds(ctx, "swa_kernel_pattern")
    return None if found is None else 1e3 * found[0] / ctx["trace"]["steps"]
