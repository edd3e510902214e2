"""Per-layer metric `kda_inputs_ms_per_step`: device milliseconds a step in
everything under scope `kda_inputs` (the KDA mixers' input shaping: its two
kernels, or XLA's chain where the kernels do not run), by scope_readers'
join of the trace's self times with the program's map. The table's own
scopes are the ones scope_readers was written with, so the sum is made here;
None against a program whose map names no such scope."""

import scope_readers

SCOPE = "kda_inputs"


def read(ctx):
    if scope_readers.table(ctx) is None:
        return None
    built, trace = scope_readers.program(), ctx["trace"]
    found = [seconds for name, (_count, seconds)
             in trace["device0"]["ops"].items()
             if built.place(name) is not None and SCOPE in built.scopes[
                 name.split(" ", 1)[0].lstrip("%")].split("/")]
    return 1e3 * sum(found) / trace["steps"] if found else None
