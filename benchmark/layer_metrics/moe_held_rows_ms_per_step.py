"""Per-layer metric `moe_held_rows_ms_per_step`: device milliseconds a step in
everything under scope `held_rows` (the expert layers finding each row's
(expert, token) and weight: `ops/moe.py` `held_rows`, in the forward, in the
forward that `model.remat` runs again and, for the weights' gradient alone,
in the backward), by scope_readers' join of the trace's self times with the
program's map, as `kda_inputs_ms_per_step.py` sums its scope. None against a
program whose map names no such scope."""

import scope_readers

SCOPE = "held_rows"


def read(ctx):
    if scope_readers.table(ctx) is None:
        return None
    built, trace = scope_readers.program(), ctx["trace"]
    found = [seconds for name, (_count, seconds)
             in trace["device0"]["ops"].items()
             if built.place(name) is not None and SCOPE in built.scopes[
                 name.split(" ", 1)[0].lstrip("%")].split("/")]
    return 1e3 * sum(found) / trace["steps"] if found else None
