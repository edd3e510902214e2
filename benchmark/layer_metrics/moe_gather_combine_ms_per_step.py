"""Per-layer metric `moe_gather_combine_ms_per_step`: device milliseconds a
step under scopes `held_gather` and `held_combine` together (the expert
layers reading their rows out of the tokens, `xf[token]`, and adding the
weighted results back to them, `.at[token].add`: `ops/moe.py`
`HeldExpertsMLP`, in the forward, in the forward that `model.remat` runs
again and in the backward, where each is the other's transpose). With
`moe_held_rows_ms_per_step` and `moe_grouped_ms_per_step` it parts the
expert layer's cost in four: finding the rows, gathering them, the grouped
products, combining. None against a program whose map names neither scope."""

import scope_sum

SCOPES = ("held_gather", "held_combine")


def read(ctx):
    found = [ms for ms in (scope_sum.ms_per_step(ctx, s) for s in SCOPES)
             if ms is not None]
    return sum(found) if found else None
