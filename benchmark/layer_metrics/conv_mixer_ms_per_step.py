"""Per-layer metric `conv_mixer_ms_per_step`: device milliseconds a step in
everything under a `conv` module (the gated short-convolution mixers of
`models/hybrid.py` `ConvMixer`: both projections and the elementwise chain,
in the forward, in the forward that `model.remat` runs again and in the
backward). None against a program whose map names no such module."""

import scope_sum


def read(ctx):
    return scope_sum.ms_per_step(ctx, "conv")
