"""Per-layer metric `moe_grouped_ms_per_step`: device milliseconds a step
under scope `grouped_product` (the expert bank's three grouped products and
their six backward ones, the Pallas kernels of `ops/grouped_matmul.py` with
the small operations that build their grid's table: `ops/moe.py`
`_grouped_bank`, in the forward, in the forward that `model.remat` runs
again and in the backward). None against a program whose map names no such
scope."""

import scope_sum


def read(ctx):
    return scope_sum.ms_per_step(ctx, "grouped_product")
