"""From a profiler trace to the few numbers the per-layer readers need.

``reduce_trace(dir, chips)`` reads the ``.xplane.pb`` that
``jax.profiler.start_trace`` wrote, with ``jax.profiler.ProfileData`` and
nothing else, and returns for the traced slice of the window:

- ``steps``: whole executions of the step program on device 0 (the module
  that takes most device time), and ``window_s`` from the first one's start
  to the last one's end;
- ``busy_s``: the UNION of the intervals in which an operation ran, averaged
  over the devices used — a union, not a sum: overlapping lines count once
  and gaps not at all;
- ``device0.ops``: ``{name: [count, seconds]}`` by each operation's SELF time
  (an operation that encloses others, such as a loop, keeps only what its
  children do not cover), so a kernel's events can be summed by name;
- ``device0.collective_exposed_s``: the part of device 0's collective
  operations during which no other operation runs there;
- ``breakdown``: the ten operations that took most time and the ten longest
  idle gaps (the host's activity in a gap is "unknown" until the program puts
  its spans on the profiler's clock).

Pure functions on ``(start, end)`` pairs do the work, so a hand-made trace
tests them (tests/benchmark).
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")


def is_collective(name: str) -> bool:
    """By the opcode, the last word of a shortened name."""
    return bool(COLLECTIVE.match(name.rsplit(" ", 1)[-1].lstrip("%")))
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: list[tuple[float, float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def measure(intervals: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: list[tuple[float, float]],
             b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The parts of ``a`` that no interval of ``b`` covers."""
    out = []
    cover = union(b)
    for lo, hi in union(a):
        at = lo
        for c, d in cover:
            if d <= at or c >= hi:
                continue
            if c > at:
                out.append((at, c))
            at = max(at, d)
        if at < hi:
            out.append((at, hi))
    return out


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    return subtract([(lo, hi)], busy)


def self_times(events: list[tuple[str, float, float]]) -> dict:
    """{name: [count, seconds]} with each event's self time: its duration less
    what the events nested inside it cover. Events are (name, start, end) of
    ONE line, on which they nest or follow one another."""
    table: dict[str, list] = {}
    stack: list[list] = []  # [name, end, self]

    def close(item):
        row = table.setdefault(item[0], [0, 0.0])
        row[0] += 1
        row[1] += max(item[2], 0.0)

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    while stack:
        close(stack.pop())
    return table


def reduce_planes(planes: dict) -> dict:
    """``planes``: {device plane name: {"ops": [(name, start_s, end_s)],
    "modules": [(name, start_s, end_s)]}}, device 0 first."""
    names = list(planes)
    first = planes[names[0]]
    by_module: dict[str, float] = {}
    for name, a, b in first["modules"]:
        by_module[name] = by_module.get(name, 0.0) + (b - a)
    if not by_module:
        raise ValueError("the trace holds no executed program on device 0")
    step_name = max(by_module, key=by_module.get)
    steps = sorted((a, b) for name, a, b in first["modules"]
                   if name == step_name)
    # an execution under way when tracing began or ended is recorded in part
    typical = sorted(b - a for a, b in steps)[len(steps) // 2]
    steps = [(a, b) for a, b in steps if b - a >= 0.8 * typical]
    lo, hi = steps[0][0], steps[-1][1]
    busy_each = []
    for plane in planes.values():
        busy_each.append(measure(clip([(a, b) for _, a, b in plane["ops"]],
                                      lo, hi)))
    ops0 = [(n, max(a, lo), min(b, hi)) for n, a, b in first["ops"]
            if min(b, hi) > max(a, lo)]
    table = self_times(ops0)
    # an enclosing operation (a loop) would cover the collectives inside it:
    # "another operation" means a leaf, one that encloses no later event
    coll = [(a, b) for n, a, b in ops0 if is_collective(n)]
    other = [(a, b) for n, a, b in _leaves(ops0) if not is_collective(n)]
    busy0 = union([(a, b) for _, a, b in ops0])
    idle = sorted(gaps(busy0, lo, hi), key=lambda g: g[0] - g[1])[:10]
    top = sorted(table.items(), key=lambda kv: -kv[1][1])[:10]
    return {
        "steps": len(steps), "step_program": step_name,
        "window_s": hi - lo,
        "busy_s": sum(busy_each) / len(busy_each),
        "device0": {
            "busy_s": busy_each[0], "ops": table,
            "collective_s": measure(coll),
            "collective_exposed_s": measure(subtract(coll, other)),
        },
        "breakdown": {
            "device_ops": [[n, row[1]] for n, row in top],
            "idle_gaps": [["unknown", b - a] for a, b in idle],
        },
    }


def _leaves(events):
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, a, b) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] >= b:
            out.append((n, a, b))
    return out


_HLO = re.compile(r"^(%[^ ]+) = .*?([a-z][a-z0-9-]*)\(")


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO instruction;
    keep the instruction's name and its opcode: ``%attn.36 custom-call``."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: str, chips: int, rehearsal: bool = False) -> dict:
    """Device planes of an ``.xplane.pb`` as ``reduce_planes`` wants them. A
    trace without one is an error, except on the CPU rehearsal, which has
    none: there the host's XLA threads stand in, so that the path is
    exercised (its numbers mean nothing)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        entry = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            for ev in line.events:
                a = ev.start_ns * 1e-9
                entry[key].append((short_name(ev.name), a,
                                   a + ev.duration_ns * 1e-9))
        planes[plane.name] = entry
    if planes:
        order = sorted(planes, key=lambda n: int(n.rsplit(":", 1)[1]))
        return {n: planes[n] for n in order[:chips]}
    if not rehearsal:
        raise ValueError(f"{path} holds no /device:TPU: plane")
    entry = {"ops": [], "modules": []}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                if ev.name.startswith("PjitFunction("):
                    entry["modules"].append((ev.name, a, b))
                elif "XLA" in line.name or "xla" in line.name:
                    entry["ops"].append((ev.name, a, b))
    if not entry["ops"]:
        entry["ops"] = list(entry["modules"])
    return {"/host:CPU": entry}


def reduce_trace(trace_dir: str, chips: int, rehearsal: bool = False) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir), chips, rehearsal))
