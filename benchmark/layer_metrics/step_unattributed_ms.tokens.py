"""Per-layer metric `step_unattributed_ms.tokens`: device milliseconds a step
in what no phase takes: the map's phase `other` and every operation the map
does not hold; see scope_readers.table."""

import scope_readers


def read(ctx):
    return scope_readers.ms(ctx, "phase", "unattributed")
