"""Per-layer metric `short_conv_ms_per_step`: device milliseconds a step
under scope `short_conv` alone (the conv mixers' elementwise chain B * u ->
taps -> C *, what a kernel would one day replace: `models/hybrid.py`
`ConvMixer`). None against a program whose map names no such scope."""

import scope_sum


def read(ctx):
    return scope_sum.ms_per_step(ctx, "short_conv")
