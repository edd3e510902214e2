"""Device milliseconds a step under ONE named scope or module of the
program's compiled step: scope_readers' join of the trace's self times with
the program's map, summed over every instruction whose path holds the name
as a whole segment (forward, the forward that `model.remat` runs again, and
backward alike), as `layer_metrics/moe_held_rows_ms_per_step.py` sums its
scope. A new file beside `scope_readers.py`, for the readers that came
after it."""

import scope_readers


def ms_per_step(ctx, scope: str):
    """None against a program without a map, or whose map names no such
    scope (the readers' contract)."""
    if scope_readers.table(ctx) is None:
        return None
    built, trace = scope_readers.program(), ctx["trace"]
    found = [seconds for name, (_count, seconds)
             in trace["device0"]["ops"].items()
             if built.place(name) is not None and scope in built.scopes[
                 name.split(" ", 1)[0].lstrip("%")].split("/")]
    return 1e3 * sum(found) / trace["steps"] if found else None
