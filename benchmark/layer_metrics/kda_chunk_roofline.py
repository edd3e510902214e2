"""Per-layer metric `kda_chunk_roofline`: the least time the chip could take
for one step's KDA chunk cores (kda_flops.py: the algorithm's operations and
bytes at a stated chunk, forward and backward once a layer, from the
configuration's keys alone) over the device time under scope `kda_chunk`
(scope_readers: the kernels, or XLA's scan, and what the compiler put round
them; a forward that activation checkpointing runs again included), in
percent. None where the configuration has no such layer, where the program
keeps no map of its step or names no such scope, and in the CPU rehearsal
(a share of a chip's peaks; an unlisted TPU kind still raises in flops.py)."""

import flops
import kda_flops
import scope_readers


def read(ctx):
    cost = kda_flops.layers_cost(ctx["config"], ctx["cell"], ctx["chips"])
    if cost is None or ctx["device_kind"] == "cpu":
        return None
    ms = scope_readers.ms(ctx, "scope", "kda_chunk")
    if not ms:
        return None
    least = flops.roofline_seconds(*cost, ctx["device_kind"])["seconds"]
    return 100.0 * least / (ms / 1e3)
