"""Shared arithmetic of the per-layer readers that read the PROGRAM'S SPANS
(``source: program_span``): the trainer's ring of timed host regions
(``pytorch_distributed_train_tpu/obs/spans.py``), which is process-wide and
outlives ``trainer.close()``, so a reader finds it after the window. The
profiler is not involved: the runner's traces record no host annotations.

A reader takes the newest run in the ring (from the last ``train.init`` on;
tests run several cells in one process), the main thread's spans only, spans
flagged ``error`` left out (the runner ends ``fit`` by raising from the last
step), and of ``train.iteration`` the last ``ctx["counters"]["steps"]``: the
window's. Per-iteration numbers are MEDIANS, not sums over the window:
``stop_trace`` holds the process for seconds inside a traced window.

Against a program that has no such spans (no ``train.init``, no parent
recorded with a span) every reader returns None. A ring that wrapped past
the run's ``train.init`` is another matter and raises: a run leaves some
1100 spans in a ring of 4096 (PERF.md), and one that outgrows it should
fail aloud, not lose its set-up metrics. The functions below the line take
a list of spans, so a hand-made ring tests them
(tests/benchmark/test_bench_span_readers.py)."""

from __future__ import annotations

import statistics
import sys
import threading


def ring():
    """The program's completed spans, oldest first, or None where the
    program keeps none. Raises where the ring has wrapped past the start of
    the newest run (``check_not_wrapped``)."""
    try:
        from pytorch_distributed_train_tpu.obs import spans
    except ImportError:
        return None
    rec = spans.get_recorder()
    events = rec.events()
    check_not_wrapped(events, rec.n, rec.capacity)
    return events


def trainer_host_ms_per_step(ctx):
    """Host milliseconds a step that the trainer's loop spends on its own
    bookkeeping: the median self time of ``train.iteration`` (its length
    less its children's: the wait for the batch, the dispatch, the log),
    plus the host work of the window's logs (``train.log`` less its device
    fetch) spread over the iterations read."""
    run = newest_run(ring())
    turns = iterations(run, ctx["counters"]["steps"])
    if not turns:
        return None
    own = self_seconds(run)
    mine = {t.seq for t in turns}
    logs = sum(own[s.seq] for s in run
               if s.name == "train.log" and s.parent_seq in mine)
    return 1e3 * (statistics.median(own[t.seq] for t in turns)
                  + logs / len(turns))


def step_dispatch_ms(ctx):
    """Milliseconds the ``train.step`` call takes when the device queue does
    not hold it: the median of the calls shorter than HALF a step's wall
    time (``step_wall_s``). The two kinds lie far apart: the runtime lets
    the host run 33 steps ahead of a v5e, so after each cadenced log's
    drain that many calls return in a few milliseconds, and every later
    one waits about a whole step for a slot (PERF.md, PR 24). A tenth of
    the median ITERATION, the first rule tried, read nothing on the chip (a
    free turn is little more than its dispatch), and a tenth of a step
    left 1.8x of room on four chips. None when every call waited; how many
    were read goes to stderr."""
    run = newest_run(ring())
    turns = iterations(run, ctx["counters"]["steps"])
    if not turns:
        return None
    limit = 0.5 * step_wall_s(ctx)
    mine = {t.seq for t in turns}
    calls = [s.dur_s for s in run
             if s.name == "train.step" and s.parent_seq in mine]
    free = [d for d in calls if d < limit]
    print(f"[span_readers] step_dispatch_ms: {len(free)} of {len(calls)} "
          f"train.step calls under {1e3 * limit:.1f} ms; the run left "
          f"{len(run)} main-thread spans", file=sys.stderr)
    return 1e3 * statistics.median(free) if free else None


def step_wall_s(ctx) -> float:
    """A step's wall time: in the traced slice, from the first step
    program's start to the last one's end over its steps. The window's own
    ``window_s / steps`` will not do in a traced run: ``stop_trace`` holds
    the process for 11-28 s inside the window, which on four chips put
    half of it above the calls that waited a whole step (PR 24). The CPU
    rehearsal has no device plane (its "steps" are XLA:CPU's thunks on a
    host line), so there the window's has to do."""
    trace = ctx.get("trace") or {}
    if (trace.get("steps") and trace.get("window_s")
            and ctx.get("device_kind") != "cpu"):
        return trace["window_s"] / trace["steps"]
    return ctx["counters"]["window_s"] / ctx["counters"]["steps"]


def setup_trainer_init_s(ctx):
    """Seconds in ``Trainer.__init__`` (``train.init``)."""
    first = first_named(newest_run(ring()), "train.init")
    return None if first is None else first.dur_s


def setup_step_compile_s(ctx):
    """Seconds of the first call of the train step (``train.compile``):
    trace, lower, compile or load from the cache, first execution."""
    first = first_named(newest_run(ring()), "train.compile")
    return None if first is None else first.dur_s


def setup_first_log_s(ctx):
    """Seconds of host work in the first cadenced log (``train.log`` less
    its ``train.log.sync``): the small host-side programs it compiles are
    why warm-up has to reach it."""
    run = newest_run(ring())
    first = first_named(run, "train.log")
    if first is None:
        return None
    return first.dur_s - sum(s.dur_s for s in run
                             if s.name == "train.log.sync"
                             and s.parent_seq == first.seq)


# ------------------------------------------------- pure, on a list of spans
def check_not_wrapped(spans, n: int, capacity: int) -> None:
    """Raise where the ring dropped spans (``n`` ever completed, over its
    ``capacity``) and no ``train.init`` is left: the newest run's start
    fell out, and every reader would return None as if the program kept no
    spans. A run that fails says why; a result line that silently lacks its
    set-up metrics does not. A program older than these readers (spans
    without ``seq``) is left alone."""
    if n <= capacity or not spans or getattr(spans[0], "seq", None) is None:
        return
    if not any(s.name == "train.init" for s in spans):
        raise RuntimeError(
            f"the span ring wrapped past the run's train.init ({n} spans "
            f"completed, capacity {capacity}): shorten the run or give "
            "obs/spans.py's recorder more room")


def newest_run(spans, thread: str | None = None) -> list:
    """The main thread's spans from the last ``train.init`` on (it closes
    after its children, so from the first span opened after it did), those
    flagged ``error`` left out. Empty without one, or where the spans carry
    no ``seq``/``parent_seq`` (a program older than these readers)."""
    thread = thread or threading.main_thread().name
    mine = [s for s in spans or [] if s.thread == thread
            and getattr(s, "seq", None) is not None]
    inits = [s.seq for s in mine if s.name == "train.init"]
    if not inits:
        return []
    return sorted((s for s in mine if s.seq >= inits[-1]
                   and not (s.args or {}).get("error")),
                  key=lambda s: s.seq)


def iterations(run: list, steps: int) -> list:
    """The last ``steps`` ``train.iteration`` spans of the run that took a
    step (the turn that finds an epoch exhausted is tagged ``epoch_end``)."""
    turns = [s for s in run if s.name == "train.iteration"
             and not (s.args or {}).get("epoch_end")]
    return turns[-int(steps):] if steps > 0 else []


def self_seconds(run: list) -> dict:
    """{seq: the span's length less that of the spans opened directly
    inside it}."""
    own = {s.seq: s.dur_s for s in run}
    for s in run:
        if s.parent_seq in own:
            own[s.parent_seq] -= s.dur_s
    return own


def first_named(run: list, name: str):
    return next((s for s in run if s.name == name), None)
