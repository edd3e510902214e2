"""Per-layer metric `exit_head_ms_per_step`: device milliseconds a step of
the head's two kernels (the events the configuration's `head_kernel_pattern`
names: `%lm_head_fwd.N` and `%lm_head_bwd.N custom-call`, one of each an
exit of the looped decoder). None where the configuration names no such
kernel or the trace holds none of its events (a program whose head keeps
the logits path)."""

import readers


def read(ctx):
    found = readers.kernel_seconds(ctx, "head_kernel_pattern")
    return None if found is None else 1e3 * found[0] / ctx["trace"]["steps"]
