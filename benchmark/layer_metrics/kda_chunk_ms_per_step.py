"""Per-layer metric `kda_chunk_ms_per_step`: device milliseconds a step in
everything under scope `kda_chunk`, the KDA kernels included; see
scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "scope", "kda_chunk")
