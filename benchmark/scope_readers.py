"""Shared arithmetic of the per-layer readers that split the device's time by
what the PROGRAM says each instruction of its compiled step is: the map the
trainer records after the first step (``pytorch_distributed_train_tpu/obs/
step_program.py``: instruction name -> the scope that made it), which is
process-wide and outlives ``trainer.close()``, joined by instruction name
with the trace's self times (``ctx["trace"]["device0"]["ops"]``, names in
``trace_reduce.short_name``'s form: ``%fusion.399 fusion``).

``table(ctx)`` gives milliseconds a step by PHASE (``forward backward
recompute head_loss optimizer grad_reduce unattributed``: a partition of
device 0's self time; ``unattributed`` is the map's phase ``other`` plus
every operation the map does not hold) and by COMPONENT (``attention ffn
experts norm embed other`` over the three model phases), and under single
scopes (``kda_chunk``). It says on stderr how much of that sits in fusions
the compiler made across phases (``mixed``: such a fusion is counted under
the phase of its own ``op_name``, its root's) and in instructions that carry
a neighbour's scope for want of their own (``borrowed``: the compiler's data
movement and the operations it rewrote under a bare name).

Against a program without the module or without a map (no step yet, or a
step whose lowering could not be read back: the ``train.program_map`` span
says why) every reader returns None. A map of ANOTHER program is another
matter and raises, as a wrapped span ring does: its instruction names would
join by accident. The functions below the line take the table's parts, so a
hand-made map tests them (tests/benchmark/test_bench_scope_readers.py)."""

from __future__ import annotations

import sys

import span_readers

PHASES = ("forward", "backward", "recompute", "head_loss", "optimizer",
          "grad_reduce", "unattributed")
MODEL_PHASES = ("forward", "backward", "recompute")
COMPONENTS = ("attention", "ffn", "experts", "norm", "embed", "other")
SCOPES = ("kda_chunk",)


def program():
    """The program's newest map of its compiled step, or None where the
    program keeps none."""
    try:
        from pytorch_distributed_train_tpu.obs import step_program
    except ImportError:
        return None
    return step_program.latest()


def table(ctx):
    """``join``'s table for this run, built once (the readers share
    ``ctx``), or None."""
    if "scope_table" not in ctx:
        trace = ctx.get("trace")
        built = program()
        ctx["scope_table"] = None
        if trace and trace.get("steps") and built is not None:
            check_same_program(built.module, trace["step_program"])
            found = join(trace["device0"]["ops"], trace["steps"], built)
            ctx["scope_table"] = found
            print(f"[scope_readers] {found['total_ms']:.3f} ms a step over "
                  f"{found['operations']} operations, "
                  f"{found['joined_pct']:.2f} % of it joined to the map of "
                  f"{built.module} "
                  f"({len(built.scopes)} scoped instructions); "
                  f"{found['mixed_ms']:.3f} ms in mixed fusions, "
                  f"{found['borrowed_ms']:.3f} ms under a borrowed scope",
                  file=sys.stderr)
    return ctx["scope_table"]


def ms(ctx, axis: str, key: str):
    """Milliseconds a step under ``key`` of the table's ``axis`` (``phase``,
    ``component`` or ``scope``), or None without a table."""
    found = table(ctx)
    return None if found is None else found[axis][key]


def setup_program_map_s(ctx):
    """Seconds of the ``train.program_map`` span: what the map costs a run
    (the step lowered again from JAX's caches, its text dumped and read)."""
    first = span_readers.first_named(
        span_readers.newest_run(span_readers.ring()), "train.program_map")
    return None if first is None else first.dur_s


# ------------------------------------------------ pure, on a table and a map
def check_same_program(module: str, step_program: str) -> None:
    """Raise where the map is not of the program the trace timed: the map's
    module (``jit_train_step``) must be the traced step program's (a TPU's
    ``jit_train_step(<fingerprint>)``; the CPU rehearsal's host events call
    it ``PjitFunction(train_step)``)."""
    core = module[4:] if module.startswith("jit_") else module
    if not core or core not in step_program:
        raise RuntimeError(
            f"the program's map is of module {module!r}, the trace's step "
            f"program is {step_program!r}: a map of another program must "
            "not be read as this one's")


def join(ops: dict, steps: int, built) -> dict:
    """``ops``: {operation name: [count, self seconds]} of device 0 over
    ``steps`` whole steps; ``built``: the program's map (``place(name)`` ->
    (phase, component, recompute) or None; ``scopes``, ``mixed``,
    ``borrowed``). Milliseconds a step."""
    phase = dict.fromkeys(PHASES, 0.0)
    component = dict.fromkeys(COMPONENTS, 0.0)
    scope = dict.fromkeys(SCOPES, 0.0)
    total = joined = mixed = borrowed = 0.0
    per_step = 1e3 / steps
    for name, (_count, seconds) in ops.items():
        ms = seconds * per_step
        total += ms
        placed = built.place(name)
        if placed is None:
            phase["unattributed"] += ms
            continue
        joined += ms
        instruction = name.split(" ", 1)[0].lstrip("%")
        if instruction in built.mixed:
            mixed += ms
        if instruction in built.borrowed:
            borrowed += ms
        where, part, _recompute = placed
        phase[where if where in phase else "unattributed"] += ms
        if where in MODEL_PHASES:
            component[part] += ms
        path = built.scopes[instruction].split("/")
        for known in SCOPES:
            if known in path:
                scope[known] += ms
    return {"phase": phase, "component": component, "scope": scope,
            "total_ms": total, "operations": len(ops), "mixed_ms": mixed,
            "borrowed_ms": borrowed,
            "joined_pct": 100.0 * joined / total if total else 0.0}
